"""The harness end to end on the CPU, with the chip check skipped: a
sound run comes out correct, and a run whose timed path is broken
underneath comes out not correct, once for each fault a one-chip query
cell can have."""
import jax
import jax.numpy as jnp
import pytest

from chipbench import run, spec

CELLS = ("ssb_sf20.flight1", "ssb_sf10.joins")
PEAKS = {"hbm_bytes_per_s": 819e9}


def half_rows(spja):
    """Half of the fact rows left out, the sum over the rest doubled."""
    def broken(*args, n_rows=None, **kw):
        half = (n_rows // 2) // 32 * 32
        return spja(*args, n_rows=half, **kw) * 2.0
    return broken


def altered(spja):
    """One answer altered where it is produced: the largest group two
    float32 steps up, the least change the guarantee forbids."""
    def broken(*args, **kw):
        out = spja(*args, **kw)
        i = jnp.argmax(jnp.abs(out))
        up = jnp.nextafter(out[i], jnp.float32(jnp.inf))
        return out.at[i].set(jnp.nextafter(up, jnp.float32(jnp.inf)))
    return broken


def falls_back(spja):
    """The fused step fails, so the server's ladder answers with another
    strategy: exact, but not the path the cell times."""
    def broken(*args, **kw):
        raise RuntimeError("fused step unavailable")
    return broken


def run_cell(name, cfg, traced=False):
    cell = spec.cell(name, spec.benchmark())
    mix = spec.traffic(cell.traffic)
    return run.measure(cell, cfg, mix, seed=2**33 + 5, seconds=0.2,
                       traced=traced, devices=jax.devices()[:1],
                       peaks=PEAKS, t0=0.0)


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [None, half_rows, altered, falls_back])
def test_fault_turns_correct_false(monkeypatch, tiny_cfg, name, fault):
    from repro.kernels import ops
    if fault is not None:
        monkeypatch.setattr(ops, "spja", fault(ops.spja))
    out = run_cell(name, tiny_cfg)
    assert out["correct"] is (fault is None), out["checks"]
    assert out["attempted"] >= 1
    assert (out["failed"] == out["attempted"]) is (fault is falls_back)
    assert out["checks"]["failed_queries"]["value"] == out["failed"]
    assert list(out)[-1] == "checks"
    e2e = {m["name"] for m in spec.cell(name, spec.benchmark()).end_to_end}
    assert set(out["metrics"]) == e2e
    assert all(m["value"] > 0 for m in out["metrics"].values())
