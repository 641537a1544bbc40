"""A whole run at a reduced size on the CPU, past the look for a chip: the
sound program comes out correct, and with the timed path broken underneath
(a token altered where it is produced, a step that leaves the KV pool as it
was, half of the batch left out) ``correct`` comes out false. The fp8
control, judged by the same comparison, comes out not correct too. The
limit here is this size's own, between its program and control readings;
``bench/control.py`` reads the cell's on the chip at the cell's size."""
import copy
import json

import pytest

import run
from repro.configs import get_config
from repro.models.model import LM
from repro.serving.scheduler import Scheduler

SPEC = json.loads((run.ROOT / "BENCHMARK.json").read_text())
LIMIT = 0.01


def _cell(name):
    """The cell at its family's CPU size, with a pool, rows and a mix to
    match."""
    cell = run.Cell(SPEC, name)
    widths, _ = cell.family.small(cell.cfg)
    cell.cfg = dict(cell.cfg, **widths, max_len=128,
                    prefill_chunk_tokens=32, kv_pool_bytes=1 << 20,
                    max_batch_seqs=4)
    cell.mix = copy.deepcopy(cell.mix)
    cell.mix["prompt"] = {"median": 40, "sigma": 0.1, "min": 36, "max": 44}
    cell.mix["output"] = {"median": 6, "sigma": 0.3, "min": 4, "max": 10}
    if "requests" in cell.mix:
        cell.mix["requests"] = 12
    cell.params = dict(cell.params, rate_rps=4.0, gap_limit=LIMIT,
                       check_tokens=40, check_max_requests=6)
    return cell


def _run(name, **kw):
    cell = _cell(name)
    _, reduced = cell.family.small(cell.cfg)
    pc = get_config(cell.cfg["program_arch"]).reduced(**reduced)
    return run.run_cell(cell, 2**31 + 5, 3.0, False, program_cfg=pc,
                        require_tpu=False, **kw)


CELL = "internlm2-1.8b.conversation"


def _altered_token(monkeypatch):
    orig = Scheduler._plan_decode

    def plan(self, r, k):
        nxt, drafts = orig(self, r, k)
        return (nxt + 1) % 512, drafts
    monkeypatch.setattr(Scheduler, "_plan_decode", plan)


def _state_unchanged(monkeypatch):
    orig = LM.step_paged_ragged

    def step(self, params, cache, tokens, ctx_lens, q_lens):
        logits, out = orig(self, params, cache, tokens, ctx_lens, q_lens)
        return logits, dict(out, **{k: v for k, v in cache.items()
                                    if k.startswith("pool_")})
    monkeypatch.setattr(LM, "step_paged_ragged", step)


def _half_batch(monkeypatch):
    orig = LM.step_paged_ragged

    def step(self, params, cache, tokens, ctx_lens, q_lens):
        logits, out = orig(self, params, cache, tokens, ctx_lens,
                           q_lens.at[q_lens.shape[0] // 2:].set(0))
        return logits, dict(out, pos=ctx_lens + q_lens)
    monkeypatch.setattr(LM, "step_paged_ragged", step)


def test_sound_run_is_correct_and_the_control_is_not():
    res = _run(CELL, control=True)
    gap = res["checks"]["max_logit_gap"]["value"]
    assert res["correct"], res["checks"]
    assert res["checks"]["tokens_checked"]["value"] >= 20
    assert list(res)[-1] == "checks"
    assert gap < LIMIT / 3 < LIMIT < res["control"]["max_logit_gap"]
    assert not res["control"]["correct"]


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged,
                                   _half_batch])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    res = _run(CELL)
    assert not res["correct"], res["checks"]
