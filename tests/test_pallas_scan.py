"""Bit-exact parity fuzz for the Pallas scan kernels (``ops.pallas_scan``).

Runs the *exact kernel program* under Pallas interpret mode on CPU
(``TEXTBLAST_PALLAS_INTERPRET=1``), so tier-1 exercises the same blocked
fori_loop / lane-roll / identity-mask schedule the TPU lowers.  Every op
here is int32 ALU with exact wraparound, so every comparison is bit-exact —
three ways where it matters: kernel vs the lax scans (``TEXTBLAST_PALLAS=off``)
vs a pure-Python host oracle.  Real-hardware runs of the compiled kernel
are marked ``slow``.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
pytest.importorskip("jax.experimental.pallas")

import jax.numpy as jnp  # noqa: E402

try:
    from textblaster_tpu.ops import pallas_scan as psc
    from textblaster_tpu.ops.dfa import dfa_states
    from textblaster_tpu.ops.stats import _poly_hash_many, hash_string
except Exception as e:  # pragma: no cover - partial jax builds
    pytest.skip(f"pallas scan stack unavailable: {e}", allow_module_level=True)

pytestmark = pytest.mark.pallas


@pytest.fixture
def interp(monkeypatch):
    """Force the interpret-mode kernel path; clear the disabling hatch."""
    monkeypatch.delenv("TEXTBLAST_PALLAS", raising=False)
    monkeypatch.setenv("TEXTBLAST_PALLAS_INTERPRET", "1")


def _full_range_int32(rng, shape):
    return rng.integers(-(2**31), 2**31, size=shape, dtype=np.int64).astype(
        np.int32
    )


# Edge documents the fuzz must cover: empty, all-whitespace, multilingual
# BMP text, astral-plane codepoints, and a row exactly at bucket length.
EDGE_TEXTS = [
    "",
    " \t\n  \r\t ",
    "The quick brown fox jumps over the lazy dog, twice.",
    "Ætt blåbærsyltetøy — grød på ærø, ÆØÅ æøå.",
    "数据处理流水线的奇偶校验测试文本，包含中文。",
    "𝔘𝔫𝔦𝔠𝔬𝔡𝔢 𝕋𝕖𝕩𝕥 🚀🔥𐍈𒀀 and some ascii",
    "a" * 256,
    "word " * 51,
]


def _rows_from_texts(texts, length):
    cps = np.zeros((len(texts), length), np.int32)
    lens = np.zeros((len(texts),), np.int32)
    for i, t in enumerate(texts):
        cp = [ord(c) for c in t][:length]
        cps[i, : len(cp)] = cp
        lens[i] = len(cp)
    return cps, lens


# --- raw kernels vs the lax twins -------------------------------------------


@pytest.mark.parametrize(
    "shape", [(8, 128), (16, 256), (8, 512), (8, 1280), (24, 1024)]
)
def test_affine_scan_matches_lax_fuzz(interp, shape):
    # Shapes cover every in-kernel block width (128/256/512) and multi-block
    # carry folding; full-range int32 inputs exercise exact wraparound.
    rng = np.random.default_rng(shape[0] * 100_003 + shape[1])
    m, a1, a2 = (_full_range_int32(rng, shape) for _ in range(3))
    assert psc.pallas_scan_ok(*shape)
    got = psc.affine_hash_scan(jnp.asarray(m), (jnp.asarray(a1), jnp.asarray(a2)))
    want = jax.lax.associative_scan(
        psc._affine_op,
        (jnp.asarray(m), jnp.asarray(a1), jnp.asarray(a2)),
        axis=1,
    )[1:]
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("n_states", [2, 5, 8])
def test_dfa_compose_scan_matches_lax_fuzz(interp, n_states):
    rng = np.random.default_rng(17 * n_states)
    shape = (16, 640)  # 640 % 512 != 0 -> 128-lane blocks, 5 carry folds
    fns = np.zeros(shape, np.int64)
    for s in range(n_states):
        fns |= rng.integers(0, n_states, size=shape) << (4 * s)
    fns = jnp.asarray(fns.astype(np.int32))
    got = psc.dfa_compose_scan(fns, n_states)
    (want,) = jax.lax.associative_scan(psc._dfa_op(n_states), (fns,), axis=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --- end-to-end through dfa.py / stats.py, three-way vs the host oracle -----


def _host_dfa(char_classes, transition, start_state):
    out = np.zeros(char_classes.shape, np.int64)
    for r in range(char_classes.shape[0]):
        s = start_state
        for j in range(char_classes.shape[1]):
            s = int(transition[char_classes[r, j], s])
            out[r, j] = s
    return out


def test_dfa_states_three_way_parity(interp, monkeypatch):
    rng = np.random.default_rng(7)
    n_sym, n_states = 7, 6  # <= 8 states: the nibble-packed kernel branch
    transition = rng.integers(0, n_states, size=(n_sym, n_states)).astype(
        np.int32
    )
    cc = rng.integers(0, n_sym, size=(16, 512)).astype(np.int32)
    assert psc.pallas_scan_ok(*cc.shape)
    kern = np.asarray(dfa_states(jnp.asarray(cc), transition, start_state=2))
    with monkeypatch.context() as m:
        m.setenv("TEXTBLAST_PALLAS", "off")
        assert not psc.pallas_scan_ok(*cc.shape)
        lax = np.asarray(dfa_states(jnp.asarray(cc), transition, start_state=2))
    np.testing.assert_array_equal(kern, lax)
    np.testing.assert_array_equal(kern, _host_dfa(cc, transition, 2))


def test_poly_hash_three_way_parity(interp, monkeypatch):
    length = 256
    cps, lens = _rows_from_texts(EDGE_TEXTS, length)
    iota = np.arange(length)[None, :]
    in_seg = jnp.asarray(iota < lens[:, None])
    seg_start = jnp.asarray((iota == 0) & (lens[:, None] > 0))
    vals = (jnp.asarray(cps), jnp.asarray(cps * 7 + 13))

    assert psc.pallas_scan_ok(*cps.shape)
    kern = [np.asarray(h) for h in _poly_hash_many(vals, in_seg, seg_start)]
    with monkeypatch.context() as m:
        m.setenv("TEXTBLAST_PALLAS", "off")
        lax = [np.asarray(h) for h in _poly_hash_many(vals, in_seg, seg_start)]
    for k, l in zip(kern, lax):
        np.testing.assert_array_equal(k, l)
    # Host oracle: the hash at each segment's last position must equal the
    # pure-Python polynomial hash of the text (empty rows have no position).
    for i, t in enumerate(EDGE_TEXTS):
        n = int(lens[i])
        if n == 0:
            continue
        assert int(kern[0][i, n - 1]) == hash_string(t[:n])


# --- gates and hatches ------------------------------------------------------


def test_shape_gate(interp):
    assert psc.pallas_scan_ok(8, 128)
    assert not psc.pallas_scan_ok(12, 256)  # rows not a multiple of 8
    assert not psc.pallas_scan_ok(16, 100)  # length not a multiple of 128
    assert not psc.pallas_scan_ok(16, 64)  # below the minimum lane tile
    assert not psc.pallas_scan_ok(0, 128)
    assert not psc.pallas_scan_ok(8, 2 * psc._MAX_LANES)


def test_escape_hatches_win_over_interpret(monkeypatch):
    monkeypatch.setenv("TEXTBLAST_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("TEXTBLAST_PALLAS", "off")
    assert not psc.pallas_scan_supported()
    monkeypatch.delenv("TEXTBLAST_PALLAS")
    assert psc.pallas_scan_supported()


def test_mesh_tracing_disables_kernels(interp):
    # Mosaic pallas_call has no GSPMD rule; a mesh-sharded trace must see
    # the kernels as unavailable and take the lax scans.
    assert psc.pallas_scan_supported()
    with psc.mesh_tracing():
        assert not psc.pallas_scan_supported()
        with psc.mesh_tracing(False):  # nesting restores per scope
            assert psc.pallas_scan_supported()
        assert not psc.pallas_scan_supported()
    assert psc.pallas_scan_supported()


# --- real hardware ----------------------------------------------------------


@pytest.mark.slow
def test_compiled_kernel_parity_on_accelerator(monkeypatch):
    """The Mosaic-compiled kernel (not interpret mode) vs lax on a real
    accelerator — skipped on CPU, where the probe declines by design."""
    monkeypatch.delenv("TEXTBLAST_PALLAS", raising=False)
    monkeypatch.delenv("TEXTBLAST_PALLAS_INTERPRET", raising=False)
    if jax.default_backend() == "cpu":
        pytest.skip("needs an accelerator backend")
    if not psc.pallas_scan_supported():
        pytest.skip("backend probe declined Pallas scans")
    rng = np.random.default_rng(3)
    m, a = (_full_range_int32(rng, (32, 2048)) for _ in range(2))
    got = psc.affine_hash_scan(jnp.asarray(m), (jnp.asarray(a),))
    want = jax.lax.associative_scan(
        psc._affine_op, (jnp.asarray(m), jnp.asarray(a)), axis=1
    )[1:]
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
