"""The decode-shaped expert kernel (``ops/pallas/expert_decode.py``) against
the grouped path and against a plain dense loop over experts.

CPU, through the Pallas interpreter, at both served expert models' toy
widths (SmallThinker: ReGLU, every expert held; LongCat: SwiGLU, a held
range with sentinel pairs).  ``held_experts_ffn`` is steered onto either
path by what ``expert_path`` answers, in the test, never by an option.

Tolerances.  float32: the kernel, the grouped products and the dense loop
sum in different orders; outputs of magnitude ~1 agree to 2e-5.  bf16: the
kernel keeps gate and up in float32 through the activation and rounds once,
the grouped path rounds gate and up to bf16 first, so the kernel is held to
the float32 dense loop on the bf16 operands no looser than the grouped path
is, and to the grouped path within bf16's rounding of the three products.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import experts
from ray_tpu.ops.experts import held_experts_ffn, reglu
from ray_tpu.ops.layers import swiglu
from ray_tpu.ops.pallas.expert_decode import ffn_slice

# T, k, E held, first, experts routed over, H, F, activation
WIDTHS = {
    "smallthinker": dict(T=8, k=2, E=8, first=0, routed=8, H=64, F=32,
                         activation=reglu),
    "longcat": dict(T=16, k=3, E=4, first=2, routed=12, H=64, F=32,
                    activation=swiglu),
}


def _layer(widths, dtype, seed=0):
    w = WIDTHS[widths]
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    E, H, F = w["E"], w["H"], w["F"]
    y = jax.random.normal(ks[0], (w["T"], H), dtype)
    wg = (jax.random.normal(ks[1], (E, H, F)) * H ** -0.5).astype(dtype)
    wu = (jax.random.normal(ks[2], (E, H, F)) * H ** -0.5).astype(dtype)
    wd = (jax.random.normal(ks[3], (E, F, H)) * F ** -0.5).astype(dtype)
    idx = jnp.argsort(jax.random.uniform(ks[4], (w["T"], w["routed"])),
                      axis=-1)[:, :w["k"]].astype(jnp.int32)
    weight = jax.random.uniform(ks[5], (w["T"], w["k"]), jnp.float32)
    return w, y, idx, weight, (wg, wu, wd)


def _run(path, monkeypatch, y, idx, weight, ws, **kw):
    monkeypatch.setattr(experts, "expert_path", lambda *a: path)
    out, pairs, hit = held_experts_ffn(y, idx, weight, *ws, **kw)
    return np.asarray(out), int(pairs), int(hit)


def _dense_loop(y, idx, weight, ws, *, first, live=None, activation):
    """Every held expert on every token, in float32, weighed by what the
    router gave the (token, expert) pair: 0 for a pair it did not pick."""
    wg, wu, wd = (w.astype(jnp.float32) for w in ws)
    yf = y.astype(jnp.float32)
    out = jnp.zeros(yf.shape, jnp.float32)
    for e in range(wg.shape[0]):
        picked = idx == first + e
        if live is not None:
            picked &= live[:, None]
        share = jnp.sum(jnp.where(picked, weight, 0.0), axis=-1)
        act = activation(yf @ wg[e], yf @ wu[e])
        out += share[:, None] * (act @ wd[e])
    return np.asarray(out)


def _tol(dtype):
    return 2e-5 if dtype == jnp.float32 else 6e-2


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_kernel_matches_the_grouped_path_and_a_dense_loop(
        widths, dtype, monkeypatch):
    w, y, idx, weight, ws = _layer(widths, dtype)
    kw = dict(first=w["first"], activation=w["activation"])
    got, pairs, hit = _run("decode_kernel", monkeypatch, y, idx, weight, ws,
                           **kw)
    grouped, g_pairs, g_hit = _run("grouped", monkeypatch, y, idx, weight,
                                   ws, **kw)
    dense = _dense_loop(y, idx, weight, ws, **kw)
    assert got.dtype == np.float32 and got.shape == y.shape
    assert (pairs, hit) == (g_pairs, g_hit) and 0 < pairs <= idx.size
    assert np.max(np.abs(got - grouped)) < _tol(dtype)
    assert np.max(np.abs(got - dense)) < _tol(dtype)
    if dtype == jnp.bfloat16:  # no precision below the grouped path's
        assert (np.max(np.abs(got - dense))
                <= np.max(np.abs(grouped - dense)) + 1e-6)
    assert np.max(np.abs(dense)) > 0.1  # there was something to compare


@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_no_pair_held_is_zeros_and_reads_no_expert(widths, monkeypatch):
    w, y, idx, weight, ws = _layer(widths, jnp.float32)
    # every weight a NaN: had one expert been read, the output would show
    ws = tuple(jnp.full_like(x, jnp.nan) for x in ws)
    idx = jnp.full_like(idx, w["first"] + w["E"])  # all past the held range
    got, pairs, hit = _run("decode_kernel", monkeypatch, y, idx, weight, ws,
                           first=w["first"], activation=w["activation"])
    assert (pairs, hit) == (0, 0) and not got.any()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_every_pair_on_one_expert_loops_over_its_tiles(
        widths, dtype, monkeypatch):
    """All T * k pairs on one expert: more rows than a tile of 16, no
    capacity, no dropped token."""
    w, y, idx, weight, ws = _layer(widths, dtype)
    idx = jnp.full_like(idx, w["first"] + 1)
    assert idx.size >= 16
    kw = dict(first=w["first"], activation=w["activation"])
    got, pairs, hit = _run("decode_kernel", monkeypatch, y, idx, weight, ws,
                           **kw)
    grouped, *_ = _run("grouped", monkeypatch, y, idx, weight, ws, **kw)
    assert (pairs, hit) == (idx.size, 1)
    assert np.max(np.abs(got - grouped)) < _tol(dtype)
    assert np.max(np.abs(got - _dense_loop(y, idx, weight, ws, **kw))) \
        < _tol(dtype)


def test_a_held_range_leaves_the_sentinel_pairs_out(monkeypatch):
    w, y, idx, weight, ws = _layer("longcat", jnp.float32)
    kw = dict(first=w["first"], activation=w["activation"])
    got, pairs, hit = _run("decode_kernel", monkeypatch, y, idx, weight, ws,
                           **kw)
    held = (idx >= w["first"]) & (idx < w["first"] + w["E"])
    assert pairs == int(held.sum()) and 0 < pairs < idx.size
    assert hit == len(np.unique(np.asarray(idx)[np.asarray(held)]))
    assert np.max(np.abs(got - _dense_loop(y, idx, weight, ws, **kw))) < 2e-5
    # a token none of whose picks is held gets nothing
    alone = ~np.asarray(held).any(axis=1)
    assert alone.any() and not got[alone].any()


@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_live_masks_a_freed_slot(widths, monkeypatch):
    w, y, idx, weight, ws = _layer(widths, jnp.float32)
    live = jnp.arange(w["T"]) % 3 != 1
    # a freed slot's row may hold anything
    y = jnp.where(live[:, None], y, jnp.nan)
    kw = dict(first=w["first"], live=live, activation=w["activation"])
    got, pairs, hit = _run("decode_kernel", monkeypatch, y, idx, weight, ws,
                           **kw)
    grouped, g_pairs, g_hit = _run("grouped", monkeypatch, y, idx, weight,
                                   ws, **kw)
    assert (pairs, hit) == (g_pairs, g_hit)
    assert np.isfinite(got).all() and not got[~np.asarray(live)].any()
    clean = jnp.where(live[:, None], y, 0.0)
    assert np.max(np.abs(
        got - _dense_loop(clean, idx, weight, ws, **kw))) < 2e-5


@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_an_expert_with_no_row_is_never_read(widths, monkeypatch):
    """NaN weights on an expert no pair landed on leave the output finite:
    the kernel copies the hit experts only."""
    w, y, idx, weight, ws = _layer(widths, jnp.float32)
    missed = w["first"] + 1
    idx = jnp.where(idx == missed, w["first"], idx)  # nobody picks it
    ws = tuple(x.at[1].set(jnp.nan) for x in ws)
    kw = dict(first=w["first"], activation=w["activation"])
    got, pairs, hit = _run("decode_kernel", monkeypatch, y, idx, weight, ws,
                           **kw)
    assert np.isfinite(got).all() and hit < w["E"]
    clean = tuple(jnp.nan_to_num(x) for x in ws)
    assert np.max(np.abs(
        got - _dense_loop(y, idx, weight, clean, **kw))) < 2e-5


def test_an_expert_wider_than_a_stage_travels_in_slices(monkeypatch):
    """F cut into column slices of gate / up and rows of down whose partial
    products add (LongCat's expert at its real widths: eight slices)."""
    from ray_tpu.ops.pallas import expert_decode as ed

    assert ffn_slice(2560, 768, 2) == 768  # SmallThinker's travels whole
    assert ffn_slice(6144, 2048, 2) == 256
    monkeypatch.setattr(ed, "_STAGE_BYTES", 3 * 128 * 128 * 4)
    assert ffn_slice(128, 512, 4) == 128
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    T, k, E, H, F = 8, 2, 4, 128, 512
    y = jax.random.normal(ks[0], (T, H))
    ws = (jax.random.normal(ks[1], (E, H, F)) * H ** -0.5,
          jax.random.normal(ks[2], (E, H, F)) * H ** -0.5,
          jax.random.normal(ks[3], (E, F, H)) * F ** -0.5)
    idx = jax.random.randint(ks[4], (T, k), 0, E)
    weight = jax.random.uniform(ks[5], (T, k))
    got, *_ = _run("decode_kernel", monkeypatch, y, idx, weight, ws,
                   first=0, activation=swiglu)
    assert np.max(np.abs(got - _dense_loop(
        y, idx, weight, ws, first=0, activation=swiglu))) < 2e-5


@pytest.mark.parametrize("case, want", [
    # SmallThinker's decode step: 32 slots x 6 picks on 64 experts
    (dict(T=32, k=6, H=2560, F=768), "decode_kernel"),
    # LongCat's: 128 slots x 12 picks, 16 experts held
    (dict(T=128, k=12, H=6144, F=2048), "decode_kernel"),
    # the long prefill buckets keep the grouped path
    (dict(T=14352, k=6, H=2560, F=768), "grouped"),
    (dict(T=4096, k=6, H=2560, F=768), "grouped"),
    (dict(T=2048, k=12, H=6144, F=2048), "grouped"),
    # small prefill buckets fall where the rule puts them: 4096 pairs
    (dict(T=512, k=6, H=2560, F=768), "decode_kernel"),
    (dict(T=1024, k=6, H=2560, F=768), "grouped"),
    (dict(T=256, k=12, H=6144, F=2048), "decode_kernel"),
    (dict(T=512, k=12, H=6144, F=2048), "grouped"),
    # rows that would not fit VMEM beside the weights
    (dict(T=4096, k=1, H=6144, F=2048), "grouped"),
    # widths Mosaic refuses (the toy presets')
    (dict(T=32, k=6, H=64, F=32), "grouped"),
    (dict(T=30, k=6, H=2560, F=768), "grouped"),
])
def test_the_rule_that_picks_the_path(case, want, monkeypatch):
    assert experts.expert_path(**case, dtype=jnp.bfloat16) == "grouped"  # cpu
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert experts.expert_path(**case, dtype=jnp.bfloat16) == want
    assert experts.expert_path(**case, dtype=jnp.float16) == "grouped"
    monkeypatch.setattr(jax, "device_count", lambda: 4)
    assert experts.expert_path(**case, dtype=jnp.bfloat16) == "grouped"
