"""A configuration's model family: ``bench/families/<model_type>.py``.

Everything in the benchmark that knows a model's block lives in one module
per family, chosen by the configuration file's ``model_type`` key, so that a
configuration of a new family is added as new files. A family module
exposes:

- ``layout(cfg) -> {path: shape}``: the parameter tree, paths as tuples of
  keys (``bench/weights.py`` fills it from the seed);
- ``logits(params, cfg, tokens, quant=None)``: the plain float32 reference
  at every position of ``tokens``, under
  ``jax.default_matmul_precision("highest")``, built on the shared
  primitives of ``bench/reference.py`` (``quant="fp8"`` is the control);
- ``step_flops(cfg, rows)`` and ``kernel_least_seconds(cfg, rows, peaks)``:
  the analytic counts of a fused step's real rows ``(ctx_len, q_len)``
  (``bench/flops.py``);
- ``program_fields(cfg) -> {attribute: value}``: what the program's
  registry entry has to hold; a dotted attribute reaches a sub-config
  (``moe.num_experts``);
- ``small(cfg) -> (cfg_overrides, program_reduced_kwargs)``: the CPU-size
  twin the tests run, as overrides of the configuration and keyword
  arguments of the program's ``ModelConfig.reduced``.

Modules are found by file path, the repo's own directory first and then
each directory a ``run.Cell`` was built with.
"""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
NAMES = ("layout", "logits", "step_flops", "kernel_least_seconds",
         "program_fields", "small")
_DIRS = [HERE]
_LOADED: dict = {}


def load(cfg: dict, where: Path | None = None):
    """The family module of configuration ``cfg``. ``where`` adds a
    directory to look in (a cell's own ``bench/families``)."""
    if str(HERE.parent) not in sys.path:
        sys.path.insert(0, str(HERE.parent))
    if where is not None and Path(where).resolve() not in _DIRS:
        _DIRS.append(Path(where).resolve())
    model_type = cfg["model_type"]
    tried = [d / f"{model_type}.py" for d in _DIRS]
    path = next((p for p in tried if p.exists()), None)
    if path is None:
        raise FileNotFoundError(
            f"no family module for model_type {model_type!r} of "
            f"{cfg.get('name')!r}: looked for "
            f"{', '.join(str(p) for p in tried)}")
    if path not in _LOADED:
        spec = importlib.util.spec_from_file_location(
            f"bench_family_{model_type}", path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = mod
        spec.loader.exec_module(mod)
        missing = [n for n in NAMES if not callable(getattr(mod, n, None))]
        if missing:
            raise AttributeError(f"family module {path} lacks {missing}")
        _LOADED[path] = mod
    return _LOADED[path]
