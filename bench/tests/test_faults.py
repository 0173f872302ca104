"""The harness's run at smoke size on the CPU (the look for a chip is
skipped), sound and with the timed path broken underneath: each fault a
serving cell on one chip can have turns ``correct`` false, and so does
the control, the reference put in the program's place with fp8 weights."""
import numpy as np
import pytest

from bench import run
from bench.tests.smoke import smoke_budget, smoke_cell

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
SEED = 2**31 + 4321
SECONDS = 1.0


CELL = "glm4-9b.reason_long_kvhalf"


def _run(open_loop=False, **kw):
    cell = smoke_cell(CELL, open_loop=open_loop, check={"requests": 6})
    return run.run_cell(cell, SEED, SECONDS, False, budget=smoke_budget(cell),
                        peaks=PEAKS, **kw)


@pytest.mark.parametrize("open_loop", [False, True])
def test_sound_run_is_correct(open_loop):
    rec = _run(open_loop)
    assert rec["correct"] is True
    assert rec["compared"]["max_logit_gap"]["value"] < 0.1
    assert list(rec)[-1] == "compared"


def _stale_kv(monkeypatch):
    """A decode step that returns its KV state unchanged."""
    from repro.models import decoder
    orig = decoder.paged_decode_step

    def step(params, cfg, pools, *a, **kw):
        logits, _ = orig(params, cfg, pools, *a, **kw)
        return logits, pools
    monkeypatch.setattr(decoder, "paged_decode_step", step)


def _half_batch(monkeypatch):
    """Half of the decode batch left out: its rows get the other half's
    tokens."""
    from repro.models import decoder
    orig = decoder.paged_decode_horizon

    def horizon(*a, **kw):
        toks, pools, pos = orig(*a, **kw)
        h = toks.shape[0] // 2
        if h:
            toks = toks.at[toks.shape[0] - h:].set(toks[:h])
        return toks, pools, pos
    monkeypatch.setattr(decoder, "paged_decode_horizon", horizon)


def _altered_token(monkeypatch):
    """Each read-back alters the first occupied slot's tokens."""
    from repro.runtime import executor
    orig = executor.PagedExecutor.decode_finish

    def finish(self, launch):
        out, new = orig(self, launch)
        occ = launch.group.occupied_slots()
        if occ:
            out = out.copy()
            out[occ[0]] = (out[occ[0]] + 1) % self.mcfg.vocab_size
        return out, new
    monkeypatch.setattr(executor.PagedExecutor, "decode_finish", finish)


@pytest.mark.parametrize("fault", [_stale_kv, _half_batch, _altered_token])
def test_faults_turn_correct_false(monkeypatch, fault):
    fault(monkeypatch)
    rec = _run()
    assert rec["correct"] is False


def test_control_is_not_correct():
    rec = _run(control=True)
    sound = rec["compared"]["max_logit_gap"]["value"]
    ctrl = rec["control"]["compared"]["max_logit_gap"]["value"]
    assert rec["correct"] is True
    assert rec["control"]["correct"] is False
    assert ctrl > 3 * sound
