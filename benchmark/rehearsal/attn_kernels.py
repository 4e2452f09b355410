"""The attention kernels' reader rehearsed on the chip, on kernels no cell
runs yet.

    python3 benchmark/rehearsal/attn_kernels.py              # one chip
    python3 benchmark/rehearsal/attn_kernels.py --describe   # no chip

At the attention call of each configuration named (`--configs`, default
`mistral-7b-v0.1-d2` and `olmoe-1b-7b-0125-d1` under the mix `sft_4k`: q
`[4, 4096, 32, 128]` with 8 kv heads, and 16 / 16 heads) it jits forward +
backward of three implementations under the program's scope `attention`:

    flash          ray_tpu.ops.attention.flash_attention, as the cells run it
    splash         JAX's splash attention, separate dq and dkv kernels
    splash_fused   the same with `use_fused_bwd_kernel=True`

traces a few steps of each with the benchmark's own `trace_window`, and
reduces the trace with the configuration's own `kernels.attn` patterns and
the readers `attn_kernel_share` / `attn_kernel_roofline` use: the event
names as the trace prints them, the calls found per kind, ms per call, each
kind's share of its roofline, the time under the scope that is no kernel
(what `attn_glue_share` reads), and what the jobs' `attention_impl` check
says of the compiled text. One JSON object per implementation on stdout,
all of them in `chiprun_out/attn_rehearsal.json`, then a table for
PERF.md. It is no cell: the driver never runs it, and it claims nothing
about the program; it is the measurement a change of kernel library is
sized from, and the proof that the reader would read it.

`--describe` compiles the same calls for a described (not attached) v5e
and prints the custom calls' names in the compiled text: which names the
patterns have to match, and whether a block geometry fits VMEM, before any
chip time is spent. The block geometries are arguments; the defaults are
the best of a sweep PR 30 made on a v5e and did not record (fetched blocks
of 1024, compute sub-blocks of 256 to 1024).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
for _path in (ROOT, BENCH_DIR):
    if _path not in sys.path:
        sys.path.insert(0, _path)

from benchlib import flops  # noqa: E402
from benchlib.checks import (attention_as_expected,  # noqa: E402
                             custom_call_names, kernel_calls)
from benchlib.spec import load_json, load_module  # noqa: E402

SCOPE = "attention"


def splash_attention(t: int, h: int, fused: bool, fwd: List[int],
                     dkv: List[int], dq: List[int]) -> Callable:
    """`[B, T, H, D]`, `[B, T, Hkv, D]` -> `[B, T, H, D]` through JAX's
    splash kernels, causal, K and V at their own head count."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash, splash_attention_mask as masks)

    sizes = splash.BlockSizes(
        block_q=fwd[0], block_kv=fwd[1], block_kv_compute=fwd[2],
        block_q_dkv=dkv[0], block_kv_dkv=dkv[1],
        block_kv_dkv_compute=dkv[2],
        block_q_dq=None if fused else dq[0],
        block_kv_dq=None if fused else dq[1],
        use_fused_bwd_kernel=fused)
    mask = masks.MultiHeadMask([masks.CausalMask((t, t))] * h)
    kernel = splash.make_splash_mha(mask, block_sizes=sizes, head_shards=1,
                                    q_seq_shards=1)

    def attend(q, k, v):
        # splash takes no scale: folded into q; its layout is [H, T, D]
        q = (q.astype(jnp.float32) * q.shape[-1] ** -0.5).astype(q.dtype)
        o = jax.vmap(kernel)(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                             jnp.swapaxes(v, 1, 2))
        return jnp.swapaxes(o, 1, 2)
    return attend


def implementations(t: int, h: int, args) -> Dict[str, Callable]:
    from ray_tpu.ops.attention import flash_attention

    return {
        "flash": lambda q, k, v: flash_attention(q, k, v, causal=True),
        "splash": splash_attention(t, h, False, args.fwd, args.dkv, args.dq),
        "splash_fused": splash_attention(t, h, True, args.fwd,
                                         args.dkv_fused, args.dq),
    }


def make_step(attend: Callable):
    """Forward + backward as a train step holds them: the gradient of a
    scalar, so that the ops' paths carry `jvp(` and `transpose(`."""
    import jax
    import jax.numpy as jnp

    def loss(q, k, v, do):
        with jax.named_scope(SCOPE):
            o = attend(q, k, v)
        return jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32))

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))


def call_of(config: Dict[str, Any], mix: Dict[str, Any]) -> Dict[str, int]:
    """`static.attention_call` as the jobs write it, on one chip."""
    return {"batch": int(mix["sequences_per_step"]),
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "seq": int(mix["tokens_per_sequence"]),
            "head_dim": flops.head_dim(config)}


def shapes_of(call: Dict[str, int]):
    b, t, hd = call["batch"], call["seq"], call["head_dim"]
    wide, narrow = (b, t, call["heads"], hd), (b, t, call["kv_heads"], hd)
    return [wide, narrow, narrow, wide]   # q, k, v, do


def compiled_text_says(hlo: str, config: Dict[str, Any]) -> Dict[str, Any]:
    """The custom calls of a compiled step by name and by kind, and what
    the jobs' `attention_impl` check makes of them."""
    calls = kernel_calls(hlo, config["kernels"]["attn"])
    return {"hlo_custom_calls": sorted(custom_call_names(hlo)),
            "hlo_calls_by_kind": calls,
            "attention_impl_check": attention_as_expected(
                "flash", "flash", calls)}


def describe(args, cells) -> int:
    """Compile for a described v5e; print the custom calls' names."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    failed = 0
    for name, config, call in cells:
        like = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=chip)
                for s in shapes_of(call)]
        for impl, attend in implementations(call["seq"], call["heads"],
                                            args).items():
            try:
                hlo = make_step(attend).lower(*like).compile().as_text()
            except Exception as e:  # noqa: BLE001 - reported per call
                failed += 1
                print(json.dumps({"config": name, "impl": impl,
                                  "error": str(e)[-600:]}), flush=True)
                continue
            print(json.dumps({
                "config": name, "impl": impl,
                "compiled_for": "v5e:2x2 described, not attached",
                **compiled_text_says(hlo, config)}), flush=True)
    return 1 if failed else 0


def measure(args, cells) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchlib import device as bdev
    from benchlib import scope_reduce
    from benchlib.peaks import peaks_for

    device = bdev.require_device(1, rehearsal=False)
    peaks = peaks_for(device["kind"])
    roofline = load_module("layer_metrics", "attn_kernel_roofline").roofline
    trace_dir = os.path.join(ROOT, ".bench_scratch", "attn_rehearsal",
                             "trace")
    results = []
    for name, config, call in cells:
        keys = jax.random.split(jax.random.key(args.seed), 4)
        q, k, v, do = (jax.random.normal(key, s, jnp.bfloat16)
                       for key, s in zip(keys, shapes_of(call)))
        baseline = None
        for impl, attend in implementations(call["seq"], call["heads"],
                                            args).items():
            row: Dict[str, Any] = {"config": name, "impl": impl,
                                   "call": call, "device": device}
            try:
                step = make_step(attend)
                hlo = step.lower(q, k, v, do).compile().as_text()
                out = jax.block_until_ready(step(q, k, v, do))   # warm-up
            except Exception as e:  # noqa: BLE001 - reported per call
                row["error"] = str(e)[-600:]
                results.append(row)
                print(json.dumps(row), flush=True)
                continue
            grads = [np.asarray(g.astype(jnp.float32)) for g in out[1]]
            if baseline is None:
                baseline = grads
            row["max_abs_diff_dq_dk_dv_against_flash"] = [
                float(np.abs(a - b).max()) for a, b in zip(grads, baseline)]
            row.update(compiled_text_says(hlo, config))

            def body():
                for _ in range(args.steps):
                    jax.block_until_ready(step(q, k, v, do))

            started = time.time()
            reduced = bdev.trace_window(trace_dir, body, (),
                                        config["kernels"])
            files = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
            scopes = scope_reduce.reduce_scopes(
                scope_reduce.from_xplane(files[0]))
            record = {"trace": reduced, "window_started_at": started,
                      "static": {"peaks": peaks, "attention_call": call}}
            kinds = reduced["kernel_s"]["attn"]
            read = roofline(record) or {}
            scope_s = scopes["bucket_s"].get(SCOPE, 0.0)
            kernel_s = sum(s for s, _ in kinds.values())
            row.update({
                "steps": args.steps,
                "events_under_scope": [
                    {"name": short, "phase": phase, "custom_call": is_call,
                     "ms_per_step": 1e3 * s / args.steps,
                     "per_step": count / args.steps}
                    for short, phase, s, count, is_call
                    in scopes["attention_ops"]],
                "calls_per_step": {kind: count / args.steps
                                   for kind, (_s, count) in kinds.items()},
                "ms_per_call": {kind: 1e3 * s / count
                                for kind, (s, count) in kinds.items()
                                if count},
                "events_as_computed": read.get("calls"),
                "roofline_by_kind": read.get("by_kind"),
                "attn_kernel_roofline": read.get("share"),
                "bound": read.get("bound"),
                "kernels_ms_per_step": 1e3 * kernel_s / args.steps,
                "scope_ms_per_step": 1e3 * scope_s / args.steps,
                "glue_ms_per_step": 1e3 * (scope_s - kernel_s) / args.steps,
                "busy_ms_per_step": 1e3 * reduced["busy_s"] / args.steps,
            })
            if not read:
                row["why_nothing"] = scope_reduce.describe_attention(record)
            results.append(row)
            print(json.dumps(row), flush=True)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "attn_rehearsal.json"), "w") as f:
        json.dump(results, f, indent=1)
    print(table(results), flush=True)
    return 1 if any("error" in r or not r.get("attn_kernel_roofline")
                    for r in results) else 0


def table(results: List[Dict[str, Any]]) -> str:
    lines = ["| call | implementation | events (per step) | kind: ms a call,"
             " share of its roofline | kernels / glue ms a step | "
             "`attn_kernel_roofline` |", "|" + " --- |" * 6]
    for r in results:
        if "error" in r:
            lines.append(f"| {r['config']} | {r['impl']} | failed: "
                         f"{r['error'][-120:]} | | | |")
            continue
        events = ", ".join(
            f"`{e['name']}` {e['phase']} x{e['per_step']:g}"
            for e in r["events_under_scope"] if e["custom_call"])
        kinds = "; ".join(
            f"{kind} {r['ms_per_call'].get(_found_as(kind), 0):.2f}, "
            f"{share:.1f}%"
            for kind, share in (r["roofline_by_kind"] or {}).items())
        lines.append(
            f"| {r['config']} | {r['impl']} | {events} | {kinds} | "
            f"{r['kernels_ms_per_step']:.2f} / {r['glue_ms_per_step']:.2f} "
            f"| {r['attn_kernel_roofline']:.2f}% |")
    return "\n".join(lines)


def _found_as(kind: str) -> str:
    return "bwd_dkv" if kind == flops.FUSED else kind


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--configs", nargs="+", default=[
        "mistral-7b-v0.1-d2", "olmoe-1b-7b-0125-d1"])
    parser.add_argument("--traffic", default="sft_4k")
    parser.add_argument("--steps", type=int, default=5)
    parser.add_argument("--seed", type=int, default=3100000001)
    parser.add_argument("--describe", action="store_true")
    triple = dict(nargs=3, type=int, metavar=("Q", "KV", "KV_COMPUTE"))
    parser.add_argument("--fwd", default=[1024, 1024, 256], **triple)
    parser.add_argument("--dkv", default=[1024, 1024, 512], **triple)
    parser.add_argument("--dkv-fused", default=[1024, 1024, 1024], **triple)
    parser.add_argument("--dq", default=[1024, 1024], nargs=2, type=int,
                        metavar=("Q", "KV"))
    args = parser.parse_args(argv)
    mix = load_json(os.path.join(BENCH_DIR, "traffic",
                                 args.traffic + ".json"))
    cells = []
    for name in args.configs:
        config = load_json(os.path.join(BENCH_DIR, "configs",
                                        name + ".json"))
        cells.append((name, config, call_of(config, mix)))
    return describe(args, cells) if args.describe else measure(args, cells)


if __name__ == "__main__":
    sys.exit(main())
