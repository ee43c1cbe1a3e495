"""The steps ``mode="auto"`` runs on a TPU, compiled for a described
(not attached) v5e chip at SF-20 shapes.

Each step is captured from a real execution over a *shape twin* of the
SF-20 database: dimension tables at SF-20 size, a short fact table with
the column encodings SF-20's 120M rows get.  The captured arguments keep
their shapes except the fact streams (and the partitioned join's probe
side), which take their SF-20 lengths.  Compiling for the chip finds
what interpret mode cannot (constructs the TPU compiler refuses, a
program that does not fit the chip's memory) at no chip time.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.sql import compile as C
from repro.sql import engine, ssb
from repro.sql import storage as ST
from repro.sql.hashtable import HashTableCache

SF = 20
HBM_BYTES = 16e9                        # one v5e chip (Cloud TPU docs)
TWIN_ROWS = 4096


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def twin():
    """SF-20 dimensions + a TWIN_ROWS fact table encoded as SF-20's is
    (each column's min/max over 120M uniform draws is its full range)."""
    rng = np.random.default_rng(0)
    n_lo, n_supp, n_cust, n_part = ssb._scale(SF)
    dims = ssb._dimensions(rng, n_supp, n_cust, n_part)
    cols = {}
    for name, lo, hi in ssb._lineorder_specs(n_part, n_supp, n_cust):
        enc = ST.encoding_from_stats(lo, hi - 1, TWIN_ROWS)
        cols[name] = ST.pack_column(
            rng.integers(lo, hi, TWIN_ROWS, dtype=np.int32), enc)
    db = ssb.Database(ST.PackedTable("lineorder", cols),
                      *(ST.pack_table(t) for t in dims), SF)
    return db, n_lo


def _words(n_rows: int, width: int) -> int:
    return -(-n_rows // (32 // width))


def _capture(monkeypatch, name: str, run):
    """Run ``run()`` with ``ops.<name>`` recording its calls; returns the
    real jitted function and the recorded (args, kwargs)."""
    real = getattr(ops, name)
    calls = []

    def rec(*args, **kwargs):
        calls.append((args, kwargs))
        return real(*args, **kwargs)

    monkeypatch.setattr(ops, name, rec)
    run()
    assert calls, f"{name} never ran"
    return real, calls


def _abstract(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _stream(width: int, n_rows: int, sharding):
    return jax.ShapeDtypeStruct((_words(n_rows, width),), jnp.int32,
                                sharding=sharding)


def _compile_fits(fn, args, kwargs):
    compiled = fn.lower(*args, **kwargs).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total <= HBM_BYTES, (total, mem)
    return compiled


def _spja_step(monkeypatch, twin, one_chip, query: str):
    db, n_lo = twin
    plan = engine.ssb_queries()[query]
    real, calls = _capture(monkeypatch, "_spja_xla", lambda: C.compile_plan(
        plan, "fused").execute(db, mode="auto", cache=HashTableCache()))
    args, kwargs = calls[-1]
    (pred_cols, bounds, keys, key_refs, tables, mults, m1, m2,
     m_refs) = args
    pw, kw, mw = kwargs["pred_widths"], kwargs["key_widths"], \
        kwargs["m_widths"]
    a = (tuple(_stream(w, n_lo, one_chip) for w in pw),
         _abstract(bounds, one_chip),
         tuple(_stream(w, n_lo, one_chip) for w in kw),
         _abstract(key_refs, one_chip), _abstract(tables, one_chip),
         _abstract(mults, one_chip), _stream(mw[0], n_lo, one_chip),
         None if m2 is None else _stream(mw[1], n_lo, one_chip),
         _abstract(m_refs, one_chip))
    assert any(w != 32 for w in pw + kw + mw)       # packed widths
    return _compile_fits(real, a, dict(kwargs, n_rows=n_lo))


def test_solo_spja_flight1_compiles_for_v5e(monkeypatch, twin, one_chip):
    _spja_step(monkeypatch, twin, one_chip, "q1.1")


def test_solo_spja_flight4_compiles_for_v5e(monkeypatch, twin, one_chip):
    _spja_step(monkeypatch, twin, one_chip, "q4.1")


def test_shared_wave_step_compiles_for_v5e(monkeypatch, twin, one_chip):
    """The 13-query wave, anchored on the 13 queries and padded to the
    16-member bucket, as the serving loop runs it."""
    db, n_lo = twin
    plans = list(engine.ssb_queries().values())
    real, calls = _capture(
        monkeypatch, "_multi_spja_xla", lambda: C.execute_shared_morsels(
            plans, db, mode="auto", cache=HashTableCache(), pad_to=16,
            anchor=plans))
    args, kwargs = calls[-1]
    (pred_cols, bounds, keys, key_refs, tables, mults, use, valid,
     m_cols, m_refs, msel) = args
    assert bounds.shape[0] == 16
    a = (tuple(_stream(w, n_lo, one_chip) for w in kwargs["pred_widths"]),
         _abstract(bounds, one_chip),
         tuple(_stream(w, n_lo, one_chip) for w in kwargs["key_widths"]),
         _abstract(key_refs, one_chip), _abstract(tables, one_chip),
         *_abstract((mults, use, valid), one_chip),
         tuple(_stream(w, n_lo, one_chip) for w in kwargs["m_widths"]),
         _abstract(m_refs, one_chip), _abstract(msel, one_chip))
    _compile_fits(real, a, dict(kwargs, n_rows=n_lo))


def test_part_join_step_compiles_for_v5e(monkeypatch, twin, one_chip):
    """q2.1's first partitioned join: no fact filter precedes it, so its
    probe side is every fact row (pow2-padded, as ``ops.part_join``
    pads it)."""
    db, n_lo = twin
    plan = engine.ssb_queries()["q2.1"]
    real, calls = _capture(monkeypatch, "_part_join_jit", lambda: C.
                           compile_plan(plan, "part").execute(
                               db, mode="auto", cache=HashTableCache()))
    args, kwargs = calls[0]
    col, rowids, groups, htk, htv, mult, ref = args
    assert not kwargs["kernel"]
    probe = jax.ShapeDtypeStruct((1 << (n_lo - 1).bit_length(),), jnp.int32,
                                 sharding=one_chip)
    a = (_stream(kwargs["width"], n_lo, one_chip), probe, probe,
         *_abstract((htk, htv, mult, ref), one_chip))
    _compile_fits(real, a, kwargs)


def test_auto_on_tpu_routes_ops_by_table(monkeypatch, twin):
    """On a TPU, ``auto`` runs an op's kernel exactly when the op is in
    ``TPU_KERNELS``; ``kernel`` and ``ref`` force their side anywhere."""
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    assert ops.TPU_KERNELS <= set(ops.OPS)
    for op in ops.OPS:
        want = "pallas" if op in ops.TPU_KERNELS else "xla"
        assert ops.impl(op, "auto") == want
        assert ops.impl(op, "kernel") == "pallas"
        assert ops.impl(op, "ref") == "xla"
    monkeypatch.setattr(ops, "TPU_KERNELS", frozenset({"spja"}))
    assert ops.impl("spja", "auto") == "pallas"
    assert ops.impl("multi_spja", "auto") == "xla"
    monkeypatch.setattr(ops, "TPU_KERNELS", frozenset())
    db, _ = twin
    plan = engine.ssb_queries()["q2.1"]
    for strategy in ("fused", "opat", "part"):
        cq = C.compile_plan(plan, strategy)
        cq.execute(db, mode="auto", cache=HashTableCache())
        assert cq.launch_config
        assert {c["impl"] for c in cq.launch_config.values()} == {"xla"}
