"""Pallas bitonic sort vs lax.sort oracle (interpret mode on CPU).

The TPU analogue of the reference's text-primitive unit tests
(utils/text.rs:261-467): the sort underlies every duplicate statistic, so its
semantics are pinned against XLA's lexicographic sort on randomized and
adversarial inputs.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from textblaster_tpu.ops.pallas_sort import _ROWS, pallas_sort3, sort3


def _oracle(k1, k2, k3):
    return jax.lax.sort(
        (jnp.asarray(k1), jnp.asarray(k2), jnp.asarray(k3)),
        dimension=1,
        num_keys=3,
    )


def _check(k1, k2, k3):
    got = pallas_sort3(jnp.asarray(k1), jnp.asarray(k2), jnp.asarray(k3),
                       interpret=True)
    want = _oracle(k1, k2, k3)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("m", [128, 256, 1024])
def test_random_rows(m):
    rng = np.random.default_rng(m)
    k1 = rng.integers(0, 2, size=(_ROWS, m)).astype(np.int32)
    k2 = rng.integers(-(2**31), 2**31, size=(_ROWS, m)).astype(np.int32)
    k3 = rng.integers(0, 50, size=(_ROWS, m)).astype(np.int32)
    _check(k1, k2, k3)


def test_duplicate_heavy_keys():
    # Few distinct hashes -> long equal runs; ties must resolve by later keys.
    rng = np.random.default_rng(7)
    m = 256
    k1 = np.zeros((_ROWS, m), np.int32)
    k2 = rng.integers(0, 4, size=(_ROWS, m)).astype(np.int32)
    k3 = rng.integers(0, 3, size=(_ROWS, m)).astype(np.int32)
    _check(k1, k2, k3)


def test_presorted_and_reversed():
    m = 128
    asc = np.tile(np.arange(m, dtype=np.int32), (_ROWS, 1))
    _check(np.zeros_like(asc), asc, asc)
    _check(np.zeros_like(asc), asc[:, ::-1].copy(), asc)


def test_multi_block_grid():
    rng = np.random.default_rng(3)
    b, m = _ROWS * 3, 128
    k1 = rng.integers(0, 2, size=(b, m)).astype(np.int32)
    k2 = rng.integers(0, 1000, size=(b, m)).astype(np.int32)
    k3 = rng.integers(0, 1000, size=(b, m)).astype(np.int32)
    _check(k1, k2, k3)


def test_sort3_dispatch_cpu_fallback():
    # On the CPU backend sort3 must route to lax.sort and agree with it.
    rng = np.random.default_rng(11)
    k1 = rng.integers(0, 2, size=(_ROWS, 128)).astype(np.int32)
    k2 = rng.integers(0, 99, size=(_ROWS, 128)).astype(np.int32)
    k3 = rng.integers(0, 99, size=(_ROWS, 128)).astype(np.int32)
    got = sort3(jnp.asarray(k1), jnp.asarray(k2), jnp.asarray(k3))
    want = _oracle(k1, k2, k3)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_sort2_packed_vs_two_operand_fallback():
    """The packed-int64 sort2 path (x64 on — the production CPU config this
    suite runs under) must agree exactly with the x64-off two-operand stable
    lax.sort (the config real-TPU lax fallbacks use)."""
    from textblaster_tpu.ops.pallas_sort import sort2

    rng = np.random.default_rng(7)
    # Row length past the Pallas support bound so sort2 takes the lax path;
    # duplicate-heavy keys exercise within-run payload ordering, and negative
    # keys the packed form's sign handling.
    b, m = 8, 1 << 15
    k1 = rng.integers(-50, 50, size=(b, m)).astype(np.int32)
    k2 = np.tile(np.arange(m, dtype=np.int32), (b, 1))
    assert jax.config.jax_enable_x64, "suite runs the production CPU config"
    got_packed = [np.asarray(x) for x in sort2(jnp.asarray(k1), jnp.asarray(k2))]
    try:
        jax.config.update("jax_enable_x64", False)
        got_two_op = [np.asarray(x) for x in sort2(jnp.asarray(k1), jnp.asarray(k2))]
    finally:
        jax.config.update("jax_enable_x64", True)
    for g, w in zip(got_packed, got_two_op):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("max_lanes, kind", [(256, "pallas_sort"), (128, "lax_sort")])
def test_sort_dispatch_is_counted(monkeypatch, max_lanes, kind):
    """chip_smoke.py fails on any row sort that left the kernel: a width
    past the gate must book as "lax_sort", never pass silently."""
    from textblaster_tpu.ops import pallas_sort as pso

    monkeypatch.setenv("TEXTBLAST_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(pso, "_MAX_SORT_LANES", max_lanes)
    k = jnp.asarray(np.random.default_rng(5).integers(0, 9, (_ROWS, 256)), jnp.int32)
    with pso.count_scan_dispatches() as counts:
        got = pso.sort2(k, k)
    assert counts == {kind: 1}
    np.testing.assert_array_equal(np.asarray(got[0]), np.sort(np.asarray(k), axis=1))
