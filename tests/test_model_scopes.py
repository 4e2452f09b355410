"""The scope vocabulary of models/transformer.py and parallel/train_step.py
(PERF.md section 3): every name reaches the lowered module's `op_name`s,
and the names are metadata only — the compiled program is the same
without them."""

import contextlib
import inspect
import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import TINY, Transformer
from ray_tpu.parallel import MeshConfig, make_mesh
from ray_tpu.parallel.train_step import make_train_step

BLOCKS = {"embed", "layers", "attn_norm", "qkv", "attention", "attn_out",
          "mlp_norm", "final_norm", "head", "loss"}
FFN = {"dense": {"mlp/gate_up", "mlp/down"},
       "moe": {"moe", "moe/router", "moe/dispatch", "moe/experts",
               "moe/combine"}}
VARIANTS = {
    "dense": TINY.replace(remat=True),
    "moe": TINY.replace(remat=True, moe_experts=4),
}
CHUNKS = {"chunked": 32, "whole": 0}
TRANSFORMS = re.compile(r"\b(?:jvp|transpose|vmap)\(([^()]*)\)")


def scopes_of(name):
    """Every scope path in one op name, the transforms JAX wraps around a
    scope (`transpose(jvp(layers))`) taken off."""
    while TRANSFORMS.search(name):
        name = TRANSFORMS.sub(r"\1", name)
    parts = name.split("/")
    return set(parts) | {"/".join(p) for p in zip(parts, parts[1:])}


def op_names(module_text):
    """The op names in a lowered module's debug locations."""
    return re.findall(r'loc\("([^"]+)"', module_text)


def scopes_in(module_text):
    return set().union(*map(scopes_of, op_names(module_text)))


def batch_for(cfg, rows):
    return {"tokens": jnp.zeros((rows, cfg.max_seq_len + 1), jnp.int32)}


def lower_grad(cfg):
    params = jax.eval_shape(lambda: Transformer.init(jax.random.key(0), cfg))
    return jax.jit(jax.grad(lambda p, b: Transformer.loss(p, b, cfg))).lower(
        params, batch_for(cfg, 2))


def lower_step(cfg):
    mesh = make_mesh(MeshConfig(data=-1))
    init_state, train_step = make_train_step(
        lambda p, b: Transformer.loss(p, b, cfg, mesh=mesh),
        Transformer.param_specs(cfg), mesh)
    # shapes and shardings are all a lowering reads: no weights are drawn
    state = jax.eval_shape(
        lambda: init_state(Transformer.init(jax.random.key(0), cfg)))
    return train_step.lower(state, batch_for(cfg, len(jax.devices())))


LOWER = {"grad_of_loss": (lower_grad, set()),
         "train_step": (lower_step, {"optimizer"})}


@pytest.mark.parametrize("chunk", CHUNKS)
@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("program", LOWER)
def test_every_scope_reaches_the_lowered_op_names(program, variant, chunk):
    lower, extra = LOWER[program]
    cfg = VARIANTS[variant].replace(loss_chunk=CHUNKS[chunk])
    hlo = lower(cfg).as_text(debug_info=True)
    found = scopes_in(hlo)
    want = BLOCKS | FFN[variant] | extra
    assert want <= found, sorted(want - found)
    # forward, backward and recomputation read off JAX's own wrappers
    assert "transpose(jvp(layers))" in hlo
    assert "rematted_computation" in hlo


@pytest.mark.parametrize("chunk", CHUNKS)
def test_pipeline_loss_carries_the_scan_vocabulary(chunk):
    """`pipeline_loss` runs the stack, the head and the cross-entropy that
    `loss` runs, so its lowered module names the same blocks; a private
    forward in the pipeline would have to repeat every scope to pass, and
    its source may not."""
    cfg = VARIANTS["dense"].replace(loss_chunk=CHUNKS[chunk])
    mesh = make_mesh(MeshConfig(data=-1, pipe=2))
    params = jax.eval_shape(lambda: Transformer.init(jax.random.key(0), cfg))
    hlo = jax.jit(jax.grad(lambda p, b: Transformer.pipeline_loss(
        p, b, cfg, mesh=mesh, n_stages=2, n_micro=2))).lower(
            params, batch_for(cfg, 4)).as_text(debug_info=True)
    found = scopes_in(hlo)
    want = BLOCKS | FFN["dense"]
    assert want <= found, sorted(want - found)
    assert "rematted_computation" in hlo
    own = inspect.getsource(Transformer.pipeline_loss)
    assert not re.search(
        r'checkpoint|named_scope\("(embed|layers|qkv|head|loss)"', own)


def lower_eval(cfg):
    params = jax.eval_shape(lambda: Transformer.init(jax.random.key(0), cfg))
    return jax.jit(lambda p, b: Transformer.loss(p, b, cfg)).lower(
        params, batch_for(cfg, 2))


# a vocabulary no other dimension of the model equals, so a matmul with it
# in its type is one of the head's
VOCAB = 272
# per loss chunk: the logits, and with a gradient asked dx and dW, taken in
# the forward scan while the logits exist -- never the logits a second time
VOCAB_DOTS = {"grad_of_loss": (lower_grad, 3), "train_step": (lower_step, 3),
              "evaluation": (lower_eval, 1)}


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("program", VOCAB_DOTS)
def test_chunked_head_recomputes_nothing(program, variant):
    lower, dots = VOCAB_DOTS[program]
    cfg = VARIANTS[variant].replace(loss_chunk=CHUNKS["chunked"],
                                    vocab_size=VOCAB)
    hlo = lower(cfg).as_text(debug_info=True)
    # the scan's body is in the module once, whatever the number of chunks
    vocab_dots = [line for line in hlo.splitlines()
                  if "stablehlo.dot_general" in line
                  and re.search(rf"(?<!\d){VOCAB}(?!\d)", line)]
    assert len(vocab_dots) == dots, vocab_dots
    rematted = [name for name in op_names(hlo)
                if "rematted_computation" in name]
    in_head = [name for name in rematted
               if {"head", "loss"} & scopes_of(name)]
    assert not in_head, in_head[:5]
    # the layers' remat is not this test's business, and still there
    assert bool(rematted) == (program != "evaluation")


def stripped(hlo_text):
    """Optimized HLO less its debug metadata: each op's `metadata={...}`,
    the header's tables of source locations, and the numbers that make
    instruction names unique (inside a shard_map an instruction is named
    after its op_name, so `%reshape.12` is `%transpose_reshape.2` without
    the scopes and every later `%reshape.N` counts from elsewhere)."""
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", hlo_text)
    text = re.sub(r"(%[\w\-]+?)(?:\.\d+)+\b", r"\1", text)
    return re.sub(r"(?ms)^FileNames$.*?^StackFrames$.*?\n\n", "", text)


@pytest.mark.parametrize("variant,chunk", [("dense", "chunked"),
                                           ("moe", "whole")])
def test_scopes_change_metadata_only(monkeypatch, variant, chunk):
    cfg = VARIANTS[variant].replace(loss_chunk=CHUNKS[chunk])
    with_scopes = lower_step(cfg).compile().as_text()
    assert "/attn_out/" in with_scopes

    @contextlib.contextmanager
    def no_scope(name):
        yield

    monkeypatch.setattr(jax, "named_scope", no_scope)
    without = lower_step(cfg).compile().as_text()
    assert "/attn_out/" not in without
    assert stripped(with_scopes) == stripped(without)
