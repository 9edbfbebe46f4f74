"""A run whose timed path is broken underneath comes out not correct: once
for each fault a one-chip cell can have (the exchange between chips does
not exist on one chip), and for the bfloat16 control."""
import dataclasses

import jax.numpy as jnp
import pytest

CELLS = [("graph500-s26", "alarms-sat")]


def _unchanged(mp):
    from repro.api.stream import GraphStream

    mp.setattr(GraphStream, "_dispatch_update_pre",
               lambda self, live, pre: (live, jnp.zeros(())))


def _half(mp):
    from repro.api.stream import GraphStream

    gs_orig = GraphStream._ingest_encoded

    def gs_half(self, s, d, w, ts, key):
        n = s.shape[0] // 2
        return gs_orig(self, s[:n], d[:n], w[:n], ts, key)

    mp.setattr(GraphStream, "_ingest_encoded", gs_half)


def _altered(mp):
    from repro.api.planner import CompiledPlan

    orig = CompiledPlan.run

    def run(self, *a, **kw):
        out = list(orig(self, *a, **kw))
        out[0] = dataclasses.replace(out[0], value=out[0].value + 1.0)
        return out

    mp.setattr(CompiledPlan, "run", run)


FAULTS = {"state_unchanged": _unchanged, "half_batch_left_out": _half, "answer_altered": _altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("config_name,mix_name", CELLS)
def test_fault_is_not_correct(run_smoke, monkeypatch, fault, config_name, mix_name):
    FAULTS[fault](monkeypatch)
    res = run_smoke(config_name, mix_name, seconds=1.0)
    assert not all(c.ok for c in res["checks"]), res["checks"]


@pytest.mark.parametrize("config_name,mix_name", CELLS + [("graph500-s26", "reach-poisson")])
def test_bf16_control_is_not_correct(run_smoke, config_name, mix_name):
    res = run_smoke(config_name, mix_name)
    assert all(c.ok for c in res["checks"])
    control = res["compare"](control=True)
    assert not all(c.ok for c in control), control
