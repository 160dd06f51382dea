"""A run with the timed path broken underneath comes out not correct: the
harness drives the tiny cell on the CPU with one fault planted in the
program each time."""
import json

import jax.numpy as jnp
import pytest

import harness
import tiny


def alter_token(cell):
    """A token altered where it is produced: each request's first decoded
    token is replaced by its neighbour in the vocabulary."""
    eng = cell.engine
    step = eng._fused_step
    V = cell.dims.V

    def fused(live, chunk_plan, specs):
        out = step(live, chunk_plan, specs)
        for r in live:
            if len(r.generated) == 2:
                r.generated[-1] = (r.generated[-1] + 1) % V
        return out
    eng._fused_step = fused


def state_unchanged(cell, monkeypatch):
    """The fused step returns the pools it was given: no K/V is kept."""
    from repro.models import api
    step = api.serve_step_paged

    def fused(params, cfg, tokens, pools, *a, **k):
        logits, _ = step(params, cfg, tokens, pools, *a, **k)
        return logits, pools
    monkeypatch.setattr(api, "serve_step_paged", fused)


def exchange_left_out(cell):
    """A restore from a peer chip brings back zeros: the ppermute's payload
    never arrives."""
    mesh = cell.mesh
    pull = mesh.pull

    def dropped(pool, donor, slots):
        return jnp.zeros_like(pull(pool, donor, slots))
    mesh.pull = dropped


@pytest.mark.parametrize("fault,chips,offload,seconds", [
    ("alter_token", 1, "host", 3),
    ("state_unchanged", 1, "host", 3),
    # the mesh legs compile per page count on first use, so give the
    # four-device cell time to park and restore
    ("exchange_left_out", 4, "fabric", 8),
])
def test_a_planted_fault_is_not_correct(tmp_path, capsys, monkeypatch,
                                        fault, chips, offload, seconds):
    root = tiny.make(tmp_path, chips=chips, offload=offload)
    plant = {"alter_token": alter_token,
             "state_unchanged": lambda c: state_unchanged(c, monkeypatch),
             "exchange_left_out": exchange_left_out}[fault]
    rc = harness.main(["--workload", "tiny-cell", "--seed", "2147483777",
                       "--seconds", str(seconds), "--trace", "0"], root=root,
                      bench_dir=root / "bench", require_tpu=False,
                      fault=plant)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert res["correct"] is False
    assert res["compared"]["worst_gap"]["value"] > tiny.LIMITS["worst_gap"]
