"""Autotune table: lookup semantics, persistence round-trip, ops consult."""
import json
import os

import numpy as np
import pytest

import jax

from repro.core import groupwise_dropout_pack
from repro.kernels import autotune, ops, ref


@pytest.fixture(autouse=True)
def _fresh_cache():
    autotune.invalidate_cache()
    yield
    autotune.invalidate_cache()


def test_lookup_defaults_without_table(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_TABLE", str(tmp_path / "missing.json"))
    got = autotune.lookup(64, 8, 4, 128, 256)
    assert got == autotune.DEFAULTS


def test_lookup_merges_partial_entry(tmp_path, monkeypatch):
    path = tmp_path / "table.json"
    key = autotune.envelope_key(64, 8, 4, 128, 256)
    path.write_text(json.dumps(
        {"version": 2, "entries": {key: {"gather_max_t": 32}}}))
    monkeypatch.setenv("REPRO_AUTOTUNE_TABLE", str(path))
    got = autotune.lookup(64, 8, 4, 128, 256)
    assert got["gather_max_t"] == 32
    assert got["tb"] == autotune.DEFAULTS["tb"]       # filled from defaults
    # unknown envelope point -> pure defaults
    assert autotune.lookup(16, 2, 1, 32, 64) == autotune.DEFAULTS


def test_envelope_key_none_bits():
    assert autotune.envelope_key(16, 2, None, 64, 128) == "16/2/None/64/128"


def test_snap_t_grid():
    assert autotune.snap_t(1) == 1
    assert autotune.snap_t(5) == 8
    assert autotune.snap_t(16) == 16
    assert autotune.snap_t(17) == 32
    assert autotune.snap_t(10_000) == autotune.T_GRID[-1]


def test_envelope_key_with_t():
    got = autotune.envelope_key(64, 8, 4, 128, 256, t=13)
    assert got == "64/8/4/128/256@T16"


def test_lookup_t_overlay_tiles_only(tmp_path, monkeypatch):
    """A v3 ``@T`` entry overlays kernel tiles only; ``gather_max_t``
    always comes from the base entry so the formulation threshold stays
    one monotone function of T (the identity contract)."""
    path = tmp_path / "table.json"
    base = autotune.envelope_key(64, 8, 4, 128, 256)
    ov = autotune.envelope_key(64, 8, 4, 128, 256, t=16)
    path.write_text(json.dumps({"version": 3, "entries": {
        base: {"tb": 128, "ob": 128, "kc": 8, "gather_max_t": 64},
        ov: {"tb": 32, "formulation": "gather", "gather_us": 1.0,
             "dense_us": 9.0, "gather_max_t": 7}}}))
    monkeypatch.setenv("REPRO_AUTOTUNE_TABLE", str(path))
    got = autotune.lookup(64, 8, 4, 128, 256, t=13)   # snaps to @T16
    assert got["tb"] == 32                            # per-T tile wins
    assert got["ob"] == 128                           # base fills the rest
    assert got["gather_max_t"] == 64   # overlay must NOT move the crossover
    # no overlay swept at this T -> pure base entry
    assert autotune.lookup(64, 8, 4, 128, 256, t=256)["tb"] == 128


def test_lookup_floors_gather_max_t(tmp_path, monkeypatch):
    """Identity floor: decode-sized batches keep the gather formulation
    (the segment dispatch always gathers) even if a stale or hand-edited
    table stores a lower crossover."""
    path = tmp_path / "table.json"
    key = autotune.envelope_key(64, 8, 4, 128, 256)
    path.write_text(json.dumps(
        {"version": 3, "entries": {key: {"gather_max_t": 4}}}))
    monkeypatch.setenv("REPRO_AUTOTUNE_TABLE", str(path))
    got = autotune.lookup(64, 8, 4, 128, 256)
    assert got["gather_max_t"] == autotune.MIN_GATHER_T


def test_corrupt_table_falls_back(tmp_path, monkeypatch):
    path = tmp_path / "bad.json"
    path.write_text("{not json")
    monkeypatch.setenv("REPRO_AUTOTUNE_TABLE", str(path))
    assert autotune.lookup(64, 8, 4, 128, 256) == autotune.DEFAULTS


def test_committed_table_loads():
    """The checked-in table must parse and yield complete entries."""
    assert os.path.exists(autotune.DEFAULT_TABLE_PATH), \
        "results/autotune_kernels.json missing (regenerate with " \
        "python -m repro.kernels.autotune)"
    entries = autotune.load_table(autotune.DEFAULT_TABLE_PATH)
    assert entries, "committed autotune table has no entries"
    for key, entry in entries.items():
        got = {**autotune.DEFAULTS, **entry}
        assert set(got) >= set(autotune.DEFAULTS), key


def test_ops_respects_tuned_tiles(tmp_path, monkeypatch):
    """A tuned (tb, ob) must flow into the kernel launch and still be
    numerically correct (padding handles non-divisible tiles)."""
    rng = jax.random.PRNGKey(0)
    delta = jax.random.normal(rng, (128, 192)) * 0.01
    p = groupwise_dropout_pack(rng, delta, h_g=128, alpha=8, k_bits=4)
    assert ops.kernel_supported(p)
    path = tmp_path / "table.json"
    key = autotune.envelope_key(p.h_g, p.keep, p.k_bits, p.h_in, p.h_out)
    path.write_text(json.dumps(
        {"version": 2,
         "entries": {key: {"tb": 32, "ob": 64, "kc": 4,
                           "gather_max_t": 4}}}))
    monkeypatch.setenv("REPRO_AUTOTUNE_TABLE", str(path))
    autotune.invalidate_cache()
    x = jax.random.normal(jax.random.PRNGKey(1), (48, 128))
    got = ops.delta_spmm(x, p, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.delta_spmm_ref(x, p)),
                               atol=1e-4, rtol=1e-4)
    # explicit arguments override the table
    got2 = ops.delta_spmm(x, p, tb=16, ob=192, interpret=True)
    np.testing.assert_allclose(np.asarray(got2),
                               np.asarray(ref.delta_spmm_ref(x, p)),
                               atol=1e-4, rtol=1e-4)


def test_col_tile_prefers_divisors():
    """Benign non-divisible h_out runs unpadded on a divisor tile (the
    fused kernel would otherwise copy-pad the whole base matrix); only
    h_out with no lane-legal divisor falls back to pad-and-slice."""
    from repro.kernels.ops import _col_tile
    assert _col_tile(256, 128) == 128     # divides: use the tuned tile
    assert _col_tile(96, 128) == 96       # divisor tile, no padding
    assert _col_tile(40, 64) == 40
    assert _col_tile(1024, 384) == 256    # largest lane-multiple divisor
    # a 96-lane divisor is not lane-legal on a TPU: pad to 128-lane tiles
    assert _col_tile(192, 128) == 128
    assert 251 % _col_tile(251, 128) != 0  # prime: pad-and-slice path
    assert _col_tile(251, 128) >= 32


def test_tile_sweep_skips_refused_packing(monkeypatch, capsys):
    """A packing outside the kernel envelope keeps the default tiles and
    says why, without timing anything (delta_spmm would run the XLA
    fallback for every candidate)."""
    rng = jax.random.PRNGKey(0)
    p = groupwise_dropout_pack(rng, jax.random.normal(rng, (64, 128)) * 0.01,
                               h_g=16, alpha=8, k_bits=4)
    assert ops.kernel_refusal(p) is not None

    def timed(*a, **k):
        raise AssertionError("a refused packing was timed")

    monkeypatch.setattr(autotune, "_time", timed)
    got = autotune._sweep_kernel_tiles(p, rng, T=8)
    assert got == {k: autotune.DEFAULTS[k] for k in ("tb", "ob", "kc")}
    assert "the kernels refuse this packing (h_g=16" in capsys.readouterr().out


def test_decode_tile_accounting():
    """Unique-tenant dedup in numbers: dup batches decode fewer tiles."""
    from repro.serve.scheduler import tenant_segments
    dup = tenant_segments(np.array([1, 1, 1, 2, 1, 1, 2, 1], np.int32))
    distinct = tenant_segments(np.arange(1, 9).astype(np.int32))
    kw = dict(n_groups=2, h_out=256, tb=8, ob=128)
    per_row = ops.per_row_decode_tiles(8, n_groups=2, h_out=256, ob=128)
    assert ops.segment_decode_tiles(dup.seg_offsets, **kw) == per_row // 4
    assert ops.segment_decode_tiles(distinct.seg_offsets, **kw) == per_row
