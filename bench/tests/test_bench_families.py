"""Model families: every configuration finds its module by ``model_type``,
a family that brings only new files is found and used by the whole harness,
and a family's parameter tree is the program's own."""
import dataclasses
import json
import textwrap

import jax
import jax.numpy as jnp
import pytest

import families
import flops
import harness
import reference
import run
import weights
from repro.configs import get_config
from repro.models import build_model

SPEC = json.loads((run.ROOT / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: json.loads((run.ROOT / c["file"]).read_text())
           for c in SPEC["configs"]}

TOY = '''
    """A toy family: the dense family's tree and reference, with counts of
    its own and a record of what called it."""
    import families

    DENSE = families.load({"model_type": "internlm2"})
    CALLS = []


    def layout(cfg):
        CALLS.append("layout")
        return DENSE.layout(cfg)


    def logits(params, cfg, tokens, quant=None):
        CALLS.append("logits")
        return DENSE.logits(params, cfg, tokens, quant)


    def step_flops(cfg, rows):
        return 1000 * sum(q for _, q in rows)


    def kernel_least_seconds(cfg, rows, peaks):
        return 1.0, 1.0, 0.0


    def program_fields(cfg):
        CALLS.append("program_fields")
        return {"d_model": cfg["hidden_size"],
                "num_layers": cfg["num_hidden_layers"],
                "mla.kv_lora_rank": None}


    def small(cfg):
        return DENSE.small(cfg)
'''


def _small(cfg):
    widths, reduced = families.load(cfg).small(cfg)
    return (dict(cfg, **widths),
            get_config(cfg["program_arch"]).reduced(**reduced))


def _toy_tree(root):
    """A configuration of family ``toy`` and its cell, written as new files
    under ``root`` alone; returns the spec that names them."""
    bench = root / "bench"
    for sub in ("families", "configs", "cells", "traffic"):
        (bench / sub).mkdir(parents=True)
    (bench / "families" / "toy.py").write_text(textwrap.dedent(TOY))
    dense = CONFIGS["internlm2-1.8b"]
    widths, _ = families.load(dense).small(dense)
    cfg = dict(dense, **widths, name="toy-0", model_type="toy")
    (bench / "configs" / "toy-0.json").write_text(json.dumps(cfg))
    (bench / "cells" / "toy-0.conversation.json").write_text(
        (run.BENCH / "cells" / "internlm2-1.8b.conversation.json")
        .read_text())
    (bench / "traffic" / "conversation.json").write_text(
        (run.BENCH / "traffic" / "conversation.json").read_text())
    return {"configs": [{"name": "toy-0", "file": "bench/configs/toy-0.json"}],
            "workloads": [{"name": "toy-0.conversation", "config": "toy-0",
                           "traffic": "conversation", "chips": 1}],
            "end_to_end": [], "per_layer": []}


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_every_config_resolves_to_a_whole_family(config):
    mod = families.load(CONFIGS[config])
    assert mod.__file__.endswith(f"{CONFIGS[config]['model_type']}.py")
    for name in families.NAMES:
        assert callable(getattr(mod, name))


def test_unknown_model_type_names_the_missing_file():
    with pytest.raises(FileNotFoundError, match=r"no_such_family\.py"):
        families.load({"model_type": "no_such_family", "name": "x"})


def test_a_family_missing_a_name_is_refused(tmp_path):
    (tmp_path / "half.py").write_text("def layout(cfg):\n    return {}\n")
    with pytest.raises(AttributeError, match="small"):
        families.load({"model_type": "half"}, tmp_path)


def test_a_family_in_new_files_is_found_and_used(tmp_path):
    """A family module and a configuration written only under a new root
    are taken up by ``run.Cell``, the weights, the reference, the counts
    and ``harness.build``, with no edit to any file of the repo."""
    cell = run.Cell(_toy_tree(tmp_path), "toy-0.conversation",
                    root=tmp_path)
    toy = cell.family
    assert toy.__file__ == str(tmp_path / "bench" / "families" / "toy.py")
    assert families.load(cell.cfg) is toy
    _, pc = _small(cell.cfg)
    model, params = harness.build(cell.cfg, 2**31 + 3, program_cfg=pc)
    assert {"program_fields", "layout"} <= set(toy.CALLS)
    tokens = jnp.arange(12, dtype=jnp.int32) % cell.cfg["vocab_size"]
    gaps = reference.gaps(params, cell.cfg, tokens)
    assert "logits" in toy.CALLS and gaps.shape == (11,)
    assert bool(jnp.all(gaps >= 0))
    assert flops.step_flops(cell.cfg, [(3, 2), (0, 5)]) == 7000
    assert flops.kernel_least_seconds(cell.cfg, [(3, 2)], {}) == (1.0, 1.0,
                                                                   0.0)
    wider = dict(cell.cfg, hidden_size=2 * cell.cfg["hidden_size"])
    with pytest.raises(RuntimeError, match="d_model"):
        harness.build(wider, 1, program_cfg=pc)


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_layout_is_the_programs_tree(config):
    """At the family's CPU size, its layout is the program's own
    parameter tree (``jax.eval_shape`` of the model's init), path for
    path and shape for shape."""
    cfg, pc = _small(CONFIGS[config])
    model = build_model(pc, param_dtype=jnp.float32,
                        compute_dtype=jnp.float32, remat=False)
    weights.check_against(jax.eval_shape(model.init, jax.random.key(0)),
                          cfg)


def test_program_fields_reach_sub_configs():
    ds = get_config("deepseek-v2-236b")
    assert harness.program_field(ds, "mla.kv_lora_rank") \
        == ds.mla.kv_lora_rank
    assert harness.program_field(ds, "moe.num_experts") \
        == ds.moe.num_experts
    assert harness.program_field(get_config("internlm2-1.8b"),
                                 "mla.kv_lora_rank") is None


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_build_refuses_a_program_that_differs(config):
    """``harness.build`` compares every field the family states: a program
    entry one off in the first whole-number width is refused, by name."""
    cfg, pc = _small(CONFIGS[config])
    name, want = next(
        (k, v) for k, v in families.load(cfg).program_fields(cfg).items()
        if "." not in k and type(v) is int)
    other = dataclasses.replace(pc, **{name: want + 1})
    with pytest.raises(RuntimeError, match=name):
        harness.build(cfg, 1, program_cfg=other)
