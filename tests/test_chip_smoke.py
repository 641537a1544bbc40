"""``chip_smoke.py``'s serving path and checks, at smoke widths on the CPU.

The script itself refuses to run without a TPU; these tests drive its
phases on the ``-smoke`` config so a change that breaks them shows up here
before it reaches the chip, and they plant faults in the served attention
to show that the logits check can fail.
"""
import importlib
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models import build_model

_SPEC = importlib.util.spec_from_file_location(
    "chip_smoke", Path(__file__).resolve().parents[1] / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(chip_smoke)

# the package the served step imports its ragged attention from at trace time
_PA = importlib.import_module("repro.kernels.paged_attention")
_ATTEND = _PA.paged_attention_ragged
CHUNK, MAX_LEN = 16, 64


def _serve_smoke(dtype, n_watch=2):
    """Serve five seeded requests through chip_smoke's engine on the
    ``-smoke`` config. Returns (model, params, requests, watch, engine,
    wall, served logits)."""
    cfg = get_config("internlm2-1.8b-smoke")
    model = build_model(cfg, param_dtype=dtype, compute_dtype=dtype,
                        remat=False)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    requests = chip_smoke.make_requests(cfg.vocab_size, 5, 4, seed=0,
                                        lens=(CHUNK, 56))
    watch = [r.rid for r in requests if len(r.prompt) > CHUNK][:n_watch]
    engine, wall, served = chip_smoke.serve(
        model, params, requests, MAX_LEN, watch, 4 << 20, CHUNK)
    return model, params, requests, watch, engine, wall, served


def test_refuses_to_run_without_a_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_smoke_serving_checks_pass_at_smoke_widths(dtype):
    model, params, requests, watch, engine, wall, served = _serve_smoke(dtype)
    assert wall > 0
    assert chip_smoke.check_served(engine, requests) == 5 * 4
    assert all(served[rid].shape[0] > 1 for rid in watch)
    diffs = chip_smoke.check_logits(model, params, requests, served, watch,
                                    MAX_LEN)
    assert sorted(diffs) == sorted(watch)
    with pytest.raises(RuntimeError, match="no served logits"):
        chip_smoke.check_logits(model, params, requests, {}, watch, MAX_LEN)


def _other_rows_pages(q, pk, pv, table, lengths, q_lens, **kw):
    """Every row reads the pages of the row before it."""
    return _ATTEND(q, pk, pv, jnp.roll(table, 1, axis=0), lengths, q_lens,
                   **kw)


def _one_wrong_page(q, pk, pv, table, lengths, q_lens, **kw):
    """The page that holds a row's chunk start is read from another row."""
    first = (lengths - q_lens) // pk.shape[1]
    hit = jnp.arange(table.shape[1])[None, :] == first[:, None]
    return _ATTEND(q, pk, pv, jnp.where(hit, jnp.roll(table, 1, axis=0),
                                        table), lengths, q_lens, **kw)


def _causal_off_by_one(q, pk, pv, table, lengths, q_lens, **kw):
    """Each chunk row also sees the key one position after its own."""
    return _ATTEND(q, pk, pv, table, lengths + (q_lens > 0), q_lens, **kw)


@pytest.mark.parametrize("fault", [_other_rows_pages, _one_wrong_page,
                                   _causal_off_by_one],
                         ids=["other-rows-pages", "one-wrong-page",
                              "causal-off-by-one"])
def test_logit_check_catches_a_planted_attention_fault(monkeypatch, fault):
    with monkeypatch.context() as m:
        m.setattr(_PA, "paged_attention_ragged", fault)
        model, params, requests, watch, engine, _, served = _serve_smoke(
            jnp.bfloat16)
    assert chip_smoke.check_served(engine, requests) == 5 * 4
    with pytest.raises(RuntimeError, match="differ"):
        chip_smoke.check_logits(model, params, requests, served, watch,
                                MAX_LEN)
