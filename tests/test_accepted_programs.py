"""The accepted cells' train steps compile to the program they compiled to
at the pin's PR (`PINS` says which; PR 59, Olmo-Hybrid-7B: a second gate in
`ops/kda.py`'s one chunked delta rule, one rule of how a sublayer's residual
is formed in `models/transformer.py`, left all nine as PR 58 had them; PR 60
changed the four cells that hold a share of the experts and no other):
each cell's step, built from `BENCHMARK.json` and
its job's `transformer_config` at the cell's own sizes, is compiled for a
described v5e and its text, with the metadata (source lines, scope names)
and the pallas kernels' bodies blanked, hashed and held to the hash the
PARENT's tree gave for the same cell (`.bench_scratch/program_text.py`,
PRs 35, 50, 53; the nine cells 34,539 / 3,117 / 8,394 / 19,774 / 12,926 /
18,945 / 10,853 / 4,821 / 12,139 lines before PR 60, Ling 36,382, Nemotron
21,311, GLM 13,387 and SDAR 11,410 since; PR 62's tiles: Ling 36,409,
Nemotron 21,301). Ling's first: it shares the delta
rule, the convolution and the head norm with the new model. PR 63
(Ouro-2.6B: a looped stack in `Transformer.hidden`, the sandwich norm by
the leaves, per-token weights with a gradient in `models/head.py` under
their own argument, `mask` as it was) left all ten as they were, SDAR's
(the weighted head), Olmo-Hybrid's (`entering` / `residual`) and d2's
(`hidden`, `_stack`) first, and pins its own cell. PR 64 (`ops/moe.
_exchange_ffn`'s ragged round hands the grouped matmuls the held experts'
groups alone) means to change Mellum2's program and no other, and found
its pin, `4d1f85c1d30e0de0` (12,139 lines), to be of a step that exchanges
nothing: `step_text` built every step under the default sharding rules,
under which that cell's experts' axis is on no mesh axis, and the text
held no `ragged-all-to-all` and came out the same from both trees. Since
PR 64 `step_text` reads `layout.rules` where a configuration states them
(Mellum2's alone does: the other ten steps are built by the calls they
were built by) and the pin is of the cell's own program. PR 65
(`ops.kda.DELTA_RESIDUALS`: one more name in `Transformer._remat`'s
policy, which wraps every layer of every cell, and a third output of the
delta rule's forward kernel) means to change Ling's program and no other:
only the pallas rule names anything, so the other ten hold. PR 66
(Xing4.0-29B-A4B: a residual path of several streams in `entering` /
`residual`, `embed` and `hidden`, a multiplier on latent attention's
softmax scale) left all eleven as they were, GLM's and Ling's first
(`latent_qkv`, the softmax scale), then d2's, Olmo-Hybrid's and Ouro's
(`entering` / `residual`, `_remat`, `embed`, `hidden`), and pins its own
cell: with `residual_streams` 1 nothing of it is traced. PR 67 (the
streams' mixing as two `jax.custom_vjp`s with pallas kernels,
`ops/mhc.enter` / `leave`; in `models/transformer.py` the two calls in
`entering` / `residual`, one more name in `_remat`'s policy,
`ops.mhc.MAPS_RESIDUALS`, and the stream carried flat from `embed` to the
exit) means to change Xing4's program and no other and makes its pin anew
from its own tree: only `enter` names anything and only a configuration
with `residual_streams` above 1 reaches it, so the other eleven hold (all
twelve run, PR 67: eleven green as they were). PR 68
(granite-4.0-h-micro on packed documents: `segment_ids` through `hidden`,
`_stack`, `_make_layer_fn`, `_make_attention`, `ops/ssm.py`'s convolution,
scan and kernels and `ops/attention.py`, the four muP scalars in `embed`,
`residual` and `models/head.py`, the scan kernels' head block a function
of the chunk too) left all twelve as they were (all thirteen run, PR 68:
without `segment_ids` nothing of it is traced, the scalars at their
neutral values neither, and at chunk 128 the head block is the sixteen it
was) and pins its own cell, whose batch holds `segment_ids`. PR 70
(`ops/kda.gated_delta_rule`, the XLA rule, keeps for its backward the
chain's per-chunk tensors and the entering states and makes the batched
stage before them again, under one `jax.checkpoint`) means to change
Olmo-Hybrid's program and no other and makes its pin anew from its own
tree: no other cell reaches the XLA rule on the chip (Ling's takes the
kernels), so the other twelve hold (all thirteen run, PR 70). Each text is
made in a process of its own (`python tests/test_accepted_programs.py
<cell>` prints its hash): inside a worker of the whole suite Ling's text
came out another than alone (its one run there read a different hash, and
took 259 s; what had run before it in that process is the one difference,
not looked into further), and a pin must not depend on what ran before it. d2 (the dense MLP, splash, `_remat`, the
chunked head, adamw: 15 s) runs with the suite; the other eleven, Ling's 75 s
first, are `slow`: `pytest tests/test_accepted_programs.py -m slow`, 9 min.

A pin is the compiler's text: it holds for the jax and libtpu that made it
(`MADE_WITH`) and the test skips under another. A PR that means to change
an accepted cell's program makes the pins anew and says so.
"""

import hashlib
import os
import re
import subprocess
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

from benchlib.spec import load_module, load_spec, resolve_cell  # noqa: E402

MADE_WITH = {"jax": "0.9.0", "libtpu": "0.0.34"}
# sha256 of the blanked text, first 16 hex digits. Which tree made each
# pin: PR 58's (the parent of PR 59, whose programs PR 59 left as they
# were) unless said. PR 60 changed the four share cells' steps (the sums
# that come back from a bounded run of expert rows, `ops/moe._by_token`)
# and made their pins anew from its own tree; the other six are PR 59's
# programs and held PR 60's tree to them. PR 62 (`ops/moe.gmm_tiles`: a
# tile of 896 for a width of 2,688, of 640 for 2,560) made Nemotron's and
# Ling's anew from its own tree; Mellum2's tiles
# changed too (896 for 896 and 1,792, 1,152 for 2,304), but they live in the kernels'
# bodies, which `blank` blanks: its text is PR 58's line for line, and its
# tiles are pinned in `tests/test_chip_compile_ep_moe.py`; OLMoE's, GLM's
# and SDAR's tiles and texts are as they were. PR 64 made Mellum2's anew
# from its own tree, built under its layout's rules for the first time
PINS = {
    # PR 65's tree: the delta rule's forward kernel writes the chunks'
    # inverses, `Transformer._remat` keeps them with o and the entering
    # states and remat's forward holds no `kda_delta_fwd` (36,582 lines;
    # its parent's, PR 62's pin, eee8e09273ef454d, 36,409)
    "train_ling3flash_ep64_d7": "878ed1082b2b7da8",
    "train_mistral7b_d2": "dc53d3934bbf1705",
    "train_olmoe_d1": "be5709d03a09969b",
    "train_nemotron3super_ep64_d11": "9c13b178970bd1b8",     # PR 62
    "train_glm47flash_ep8_d5": "a536c17835e12988",           # PR 60
    "train_phi4miniflash_d6": "a8abf884315bc039",
    "train_sdar30b_ep8_d4": "7cc6dbdd7647da51",              # PR 60
    "train_mistral7b_d8_fsdp4": "d58dfb898bcd436d",
    # PR 64's tree, with the layout's rules (23,869 lines; its parent's
    # with them 849734f4a1c00937, 24,195 lines)
    "train_mellum2_ep4_d4": "3553e1c05d2e9563",
    # PR 70's tree: the XLA delta rule makes its batched stage again in
    # its backward, and the text holds no compiler `.remat` clone (15,789
    # lines; its parent's, PR 60's pin of PR 59's tree, 1ebd73113b090dc6,
    # 15,029)
    "train_olmohybrid7b_tp2_d4": "270c7b96cd9ead7e",
    # PR 63's own cell, pinned from its own tree (4,739 lines)
    "train_ouro26b_d8": "079113b2a5d63a41",
    # PR 67's tree: the mixing of the streams is `ops/mhc.enter` /
    # `leave`, four pallas kernels on a flat stream (45,373 lines; its
    # parent's, PR 66's pin of its own cell, 907116026b0cc671, 47,363)
    "train_xing4_ep8_d5": "cad71bc5e10b5a59",
    # PR 68's own cell, pinned from its own tree: the packed step, the
    # ids in the batch (9,063 lines)
    "train_granite4hmicro_d10_packed": "ae723346c45a0d36",
}
WITH_THE_SUITE = ("train_mistral7b_d2",)


def described_host():
    """The four described devices of a v5e host, or why there are none
    to describe or the pins do not hold here."""
    from importlib import metadata

    from jax.experimental import topologies
    try:
        have = {"jax": jax.__version__, "libtpu": metadata.version("libtpu")}
    except metadata.PackageNotFoundError as e:
        return None, f"no TPU compiler installed: {e}"
    if have != MADE_WITH:
        return None, f"the pins were made with {MADE_WITH}, this is {have}"
    try:
        return list(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices), None
    except Exception as e:  # noqa: BLE001 - no TPU compiler installed
        return None, f"cannot describe a v5e topology here: {e}"


def blank(text: str) -> str:
    """The compiled text without what names a source line, a scope or a
    kernel's body (which holds the paths of the kernel's call stack)."""
    text = re.sub(r"kernel_metadata=\{\n[^\n]*\n\}", "kernel_metadata={}",
                  text)
    text = re.sub(r'(custom_call_target="tpu_custom_call"[^\n]*?)'
                  r'backend_config=[^\n]*', r"\1backend_config=BLANK", text)
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    text = re.sub(r",? ?stack_frame_id=\d+", "", text)
    return re.sub(r"(?ms)^FileNames$.*?^StackFrames$.*?\n\n", "", text)


def step_text(cell: str, devices) -> str:
    """The cell's train step as its job builds it, compiled for the
    described chips."""
    import optax

    from ray_tpu.models import Transformer, diffusion
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.parallel.train_step import make_train_step

    ctx = resolve_cell(load_spec(), cell)
    model, mix = ctx["config"], ctx["traffic"]
    seq, seqs = mix["tokens_per_sequence"], mix["sequences_per_step"]
    job = load_module("jobs", model["job"])
    cfg = job.transformer_config(model, model["train"], seq)
    mesh = make_mesh(MeshConfig(**model["layout"]["mesh"]),
                     devices=devices[:ctx["cell"]["chips"]])
    # the layout's sharding rules where it states any (Mellum2's lay the
    # experts' axis on the mesh: without them no row is exchanged)
    rules = {"rules": job.sharding_rules(model["layout"])} \
        if model["layout"].get("rules") else {}
    frozen = Transformer.frozen(cfg)
    opt = model["train"]["optimizer"]
    optimizer = optax.adamw(opt["learning_rate"],
                            weight_decay=opt["weight_decay"])
    if cfg.block_length:
        def loss(p, b):
            return Transformer.loss(p, diffusion.noised(b, cfg), cfg,
                                    mesh=mesh, with_metrics=True)
        batch = {"tokens": jax.ShapeDtypeStruct((seqs, seq), jnp.int32),
                 "noise_key": jax.ShapeDtypeStruct((seqs, 2), jnp.uint32)}
    else:
        def loss(p, b):
            return Transformer.loss(p, b, cfg, mesh=mesh, **rules,
                                    **({"with_metrics": True}
                                       if cfg.moe_experts or cfg.exit_gate
                                       else {}))
        batch = {"tokens": jax.ShapeDtypeStruct((seqs, seq + 1), jnp.int32)}
        if mix.get("documents"):   # a packed cell: the ids beside the tokens
            batch["segment_ids"] = batch["tokens"]
    _, train_step = make_train_step(
        loss, Transformer.param_specs(cfg), mesh, optimizer=optimizer,
        **rules,
        **({"frozen": frozen} if any(jax.tree.leaves(frozen)) else {}))

    def init(key):
        params = Transformer.init(key, cfg)
        return {"params": params, "opt_state": optimizer.init(params),
                "step": jnp.zeros((), jnp.int32)}

    state = jax.eval_shape(init, jax.random.key(0))
    return blank(train_step.lower(state, batch).compile().as_text())


def text_hash(cell: str) -> str:
    """`<sha256's first 16 digits> <lines>` of the cell's blanked text, or
    `skip: <why>`; the persistent compile cache off (an entry written for
    a described chip cannot be read back without one)."""
    devices, why = described_host()
    if devices is None:
        return "skip: " + why
    jax.config.update("jax_enable_compilation_cache", False)
    text = step_text(cell, devices)
    return (f"{hashlib.sha256(text.encode()).hexdigest()[:16]} "
            f"{len(text.splitlines())}")


@pytest.mark.parametrize("cell", [
    pytest.param(cell, marks=() if cell in WITH_THE_SUITE
                 else pytest.mark.slow) for cell in PINS])
def test_an_accepted_cells_step_is_the_parents_program(cell):
    done = subprocess.run(
        [sys.executable, os.path.abspath(__file__), cell],
        capture_output=True, text=True, timeout=900, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
            [ROOT, os.path.join(ROOT, "tests")]),
            ALLOW_MULTIPLE_LIBTPU_LOAD="1"))
    assert done.returncode == 0, done.stderr[-2000:]
    said = done.stdout.strip().splitlines()[-1]
    if said.startswith("skip: "):
        pytest.skip(said)
    assert said.split()[0] == PINS[cell], (cell, said)


def test_blanking_leaves_the_program_and_takes_the_names():
    text = ('%f = f32[2]{0} fusion(%a), kind=kLoop, metadata={op_name="x/'
            'y" source_file="/a/b.py" source_line=3}, stack_frame_id=7\n'
            '%k = f32[2]{0} custom-call(%f), custom_call_target='
            '"tpu_custom_call", backend_config={"body": "/a/b.py"}\n')
    assert blank(text) == (
        '%f = f32[2]{0} fusion(%a), kind=kLoop\n'
        '%k = f32[2]{0} custom-call(%f), custom_call_target='
        '"tpu_custom_call", backend_config=BLANK\n')
    assert blank(text) == blank(text.replace("/a/b.py", "/c/d/e.py")
                                .replace("x/y", "z").replace("=7", "=9"))


if __name__ == "__main__":
    print(text_hash(sys.argv[1]))
