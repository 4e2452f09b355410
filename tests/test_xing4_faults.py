"""Each fault of `benchmark/reference/xing4_faults.py` moves what it is
listed under (`LISTED_UNDER`): on the CPU, in float32, at a small size, a
fault reads at least 100 times the 1e-4 that `tests/test_xing4_reference.py`
holds the system to, by the maps' or the logits' relative L2; what is NOT
a fault (`exit_mean`: a mean before an RMSNorm is the sum's function)
reads under it; the narrower precisions order themselves. One compile a
variant (the reference under `jax.jit`)."""

import functools
import inspect

import jax
import numpy as np
import pytest

from tests._xing4 import (batch, config, faults, job, published, ref,
                          rel_l2, weights)

SHOWS = 1e-2     # a fault: 100 x the 1e-4 the system is held to
HIDDEN = 1e-5


@functools.lru_cache(maxsize=None)
def side(name):
    """The variant's (logits, maps) on seeded weights and tokens."""
    cfg = config(4, 8)
    params = weights(config(), 7)
    lay = dict(params["layers"])
    for leaf in ("w_moe_gateup", "w_moe_down"):
        lay[leaf] = lay[leaf][:, 8:12]
    w = job.to_reference_layout(dict(params, layers=lay), cfg)
    module, model, w = faults.variant(name, published(cfg), w)
    tokens = batch(cfg, 7)[:, :-1]
    logits, maps = jax.jit(lambda w_: module.forward(
        w_, tokens, model, with_maps=True))(w)
    return np.asarray(logits), np.asarray(maps)


def reading(name):
    logits, maps = side(name)
    base, base_maps = side(None)
    by_sublayer = [rel_l2(m, b) for m, b in zip(maps, base_maps)]
    return {"logits_rel_l2": rel_l2(logits, base),
            "maps_rel_l2": max(by_sublayer[:job.judged_sublayers(
                config())])}


@pytest.mark.parametrize("name", faults.FAULTS)
def test_a_fault_moves_what_it_is_listed_under(name):
    assert reading(name)[faults.LISTED_UNDER[name]] >= SHOWS, reading(name)


@pytest.mark.parametrize("name", faults.NOT_A_FAULT)
def test_what_is_no_fault_shows_nowhere(name):
    got = reading(name)
    assert got["logits_rel_l2"] <= HIDDEN and got["maps_rel_l2"] == 0.0, got


def test_narrower_operands_order_themselves():
    bf16, e4m3, e5m2 = (reading(name)["logits_rel_l2"]
                        for name in faults.PRECISIONS)
    assert 0 < bf16 < e4m3 < e5m2
    assert e4m3 >= SHOWS


def test_every_listed_fault_is_judged_and_the_reference_stays_plain():
    assert set(faults.LISTED_UNDER) == set(faults.FAULTS) | {
        "float8_e4m3fn", "float8_e5m2"}
    tol = {"logits_rel_l2": 0.05, "loss_abs": 0.01, "maps_rel_l2": 0.02}
    row = {"rel_l2": 0.01, "loss_diff": 0.001, "maps_rel_l2": 0.03}
    assert faults.judged_row(row, tol)["correct"] is False
    assert faults.judged_row(dict(row, maps_rel_l2=0.01),
                             tol)["correct"] is True
    with pytest.raises(KeyError):
        faults.variant("no_such_fault", {}, {})
    source = inspect.getsource(ref)
    assert "ray_tpu" not in source.replace("`ray_tpu`", "")
    assert "lax.scan" not in source and "pallas" not in source
    for name in ("rms_norm", "linear", "sinkhorn", "stream_maps",
                 "normed_stream", "enter_streams", "leave_streams",
                 "hyper_connected", "softmax_scale", "yarn_inv_freq",
                 "apply_rope"):
        assert getattr(ref, name).__module__ == ref.__name__
        assert not [p for p in inspect.signature(
            getattr(ref, name)).parameters if "dtype" in p], name
