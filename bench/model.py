"""A configuration file, and the architecture module it names.

A configuration file (``bench/configs/<name>.json``) holds the published
``config.json`` keys of a model as it is run here (depth cut, widths as
published), plus ``arch`` (the module under ``bench/arch/`` that maps,
draws, references and counts this architecture; its interface is in
``bench/arch/__init__.py``), ``source``, ``reduced`` (every key changed
from the source, with ``published`` giving the source's value),
``assumed`` (values the source does not state) and ``deployment`` (what
the cut stands for).

What here is about no architecture: loading a configuration, the weight
seed, the check of a parameter tree against the program's layout, and
finding a configuration's architecture module.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
_ARCHS: dict = {}     # module path -> loaded module, one load a process


def _arch_path(conf: dict, root: pathlib.Path) -> pathlib.Path:
    name = conf.get("arch")
    if not name:
        raise ValueError("the configuration names no architecture "
                         "(its \"arch\" key)")
    path = root / "arch" / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"no architecture module {name!r} "
                         f"(looked for {path.relative_to(root.parent)})")
    return path


def load_config(name: str, root: pathlib.Path = BENCH) -> dict:
    """``<root>/configs/<name>.json``; raises unless it names an
    architecture module that exists under ``<root>/arch/``."""
    with open(root / "configs" / f"{name}.json") as f:
        conf = json.load(f)
    _arch_path(conf, root)
    return conf


def arch(conf: dict, root: pathlib.Path = BENCH):
    """The architecture module ``<root>/arch/<conf["arch"]>.py``, loaded
    by file path (once a process, so its compiled reference is kept)."""
    path = _arch_path(conf, root)
    mod = _ARCHS.get(path)
    if mod is None:
        spec = importlib.util.spec_from_file_location(
            f"bench_arch_{path.stem}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _ARCHS[path] = mod
    return mod


def weight_seed(seed: int) -> int:
    """A 32-bit PRNG seed that depends on every bit of the run's seed."""
    return int(np.random.SeedSequence([int(seed), 0x77]).generate_state(1)[0])


def check_layout(params, cfg):
    """Raise unless ``params`` has exactly the program's tree, shapes and
    dtypes for ``cfg`` (its own ``init_params``, traced for shapes only)."""
    import jax
    from repro.models import transformer as T
    want = jax.eval_shape(lambda k: T.init_params(cfg, k, cfg.dtype),
                          jax.random.PRNGKey(0))
    got = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), params)
    exp = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), want)
    if got != exp:
        raise ValueError(f"weights do not match the program's layout for "
                         f"{cfg.name}: {got} != {exp}")
