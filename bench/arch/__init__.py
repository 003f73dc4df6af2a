"""Architecture modules: the one place that knows a configuration's block.

A configuration file names its module under ``"arch"``, a path from the root
of the checkout (``bench/arch/dense_gqa.py``) or an absolute one. The module
supplies:

* ``program_config(c)`` -- the program's ``ModelConfig``, checked against the
  file; the only entry point that imports the program;
* ``layout(c)`` -- ``{leaf: (shape, init)}`` in the layout the serving
  program consumes (``bench.weights`` draws it);
* ``shapes(c)`` -- the work counts: ``weight_bytes()``,
  ``kv_bytes_per_token``, ``decode(contexts, counts)`` and
  ``prefill(start, end, emits)``;
* ``COUNTERS`` -- names of backend counters; ``counts`` is their change
  across one decode call;
* ``Reference(c, seed, quant=False)`` with ``.logits(tokens, rows)`` -- the
  plain float32 forward pass, which imports nothing of the program.

A new architecture arrives as a new module and configuration files; no file
that reads them changes.
"""
from __future__ import annotations

import importlib
import importlib.util
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def path_of(c: dict, root: Path = ROOT) -> Path:
    """The configuration's architecture module, as an absolute path."""
    return (Path(root) / c["arch"]).resolve()


def load(c: dict):
    """The module named by ``c["arch"]``. One under this checkout is
    imported by its dotted name, so every caller shares one module."""
    path = path_of(c)
    try:
        rel = path.relative_to(ROOT)
    except ValueError:
        name = "bench_arch_" + "_".join(path.with_suffix("").parts[1:])
        if name not in sys.modules:
            spec = importlib.util.spec_from_file_location(name, path)
            mod = importlib.util.module_from_spec(spec)
            sys.modules[name] = mod
            try:
                spec.loader.exec_module(mod)
            except BaseException:
                del sys.modules[name]
                raise
        return sys.modules[name]
    return importlib.import_module(".".join(rel.with_suffix("").parts))
