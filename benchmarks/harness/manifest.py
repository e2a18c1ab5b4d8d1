"""``BENCHMARK.json`` -> the files of one cell, by name.

Everything that belongs to one configuration, one traffic mix, one
generator, one runner, one per-layer metric or one *architecture* sits in
a file of its own, found by the name the manifest (or the file that names
it) gives. A later PR adds files and entries and edits nothing here. A
name that resolves to no file is an error that says which file was looked
for.

An architecture is two files, and a configuration names both:

* ``"reference": "<name>"`` -> ``references/<name>.py``, the one place
  that knows the architecture: ``Arch`` (``from_model(config)``,
  ``leaf_table()``, and the attributes ``num_hidden_layers`` and
  ``vocab_size``), ``forward_logits``, ``loss_and_grads``,
  ``CHECK_LAYER_LEAVES`` / ``CHECK_TOP_LEAVES`` and
  ``train_flops_per_token`` (``references/mistral.py`` is the pattern);
* ``"published": "<name>"`` -> ``published/<name>.json``: the source URL
  and the values of the model's own ``config.json`` (``config``), against
  which the manifest test holds every configuration that names it.

Two keys and not one, because one set of layer equations serves several
published models. ``benchmarks/README.md`` says how to add an architecture.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import sys
from typing import Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmarks")


class MissingPiece(LookupError):
    pass


def load_manifest(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _need(path: str, what: str) -> str:
    if not os.path.isfile(path):
        raise MissingPiece(f"{what}: no file {os.path.relpath(path, ROOT)}")
    return path


def _find(kind_dir: str, name: str, ext: str, bench_dir: str) -> str:
    """``<bench_dir>/<kind_dir>/<name><ext>``; a fixture directory (the CPU
    tests' ``bench_dir``) is searched first, the benchmark's own after."""
    for base in dict.fromkeys((bench_dir, BENCH_DIR)):
        path = os.path.join(base, kind_dir, name + ext)
        if os.path.isfile(path):
            return path
    return _need(os.path.join(BENCH_DIR, kind_dir, name + ext),
                 f"{kind_dir} {name!r}")


def load_json(kind_dir: str, name: str, bench_dir: str = BENCH_DIR) -> Dict:
    path = _find(kind_dir, name, ".json", bench_dir)
    with open(path) as f:
        return json.load(f)


_BY_PATH: Dict[str, object] = {}


def load_module(kind_dir: str, name: str, bench_dir: str = BENCH_DIR):
    """Import ``<bench_dir>/<kind_dir>/<name>.py`` by path (a metric's name
    may hold dots and dashes, so it is not a module name). One module
    object per file, and for a file of the ``benchmarks`` package with a
    module's name the package's own (``benchmarks.references.mistral``): a
    reference's ``Arch`` keys the caches of jitted programs, so it has to
    be one class however it was reached."""
    path = _find(kind_dir, name, ".py", bench_dir)
    if path not in _BY_PATH:
        if name.isidentifier() and os.path.dirname(os.path.dirname(
                path)) == BENCH_DIR:
            mod = importlib.import_module(f"benchmarks.{kind_dir}.{name}")
        else:
            spec = importlib.util.spec_from_file_location(
                f"_bench_{kind_dir}_{name}".replace(".", "_")
                .replace("-", "_"), path)
            mod = importlib.util.module_from_spec(spec)
            sys.modules[spec.name] = mod    # a dataclass looks itself up
            spec.loader.exec_module(mod)
        _BY_PATH[path] = mod
    return _BY_PATH[path]


def reference_of(config: Dict, bench_dir: str = BENCH_DIR):
    """The reference module a configuration names (``"reference"``)."""
    return load_module("references", _named(config, "reference"), bench_dir)


def published_of(config: Dict, bench_dir: str = BENCH_DIR) -> Dict:
    """The published file a configuration names (``"published"``)."""
    return load_json("published", _named(config, "published"), bench_dir)


def _named(config: Dict, key: str) -> str:
    if key not in config:
        raise MissingPiece(f"configuration {config.get('name')!r} has no "
                           f"{key!r} key: the name of its architecture's "
                           f"file under benchmarks/ ({key} ...)")
    return config[key]


def cell(manifest: Dict, workload: str) -> Dict:
    for w in manifest["workloads"]:
        if w["name"] == workload:
            return w
    raise MissingPiece(f"workload {workload!r} is not in BENCHMARK.json "
                       f"(it has {[w['name'] for w in manifest['workloads']]})")


def resolve(manifest_path: str, workload: str):
    """``(manifest, bench_dir, cell, configuration, traffic)`` of one cell.
    ``bench_dir`` is where the CPU tests' fixture manifest keeps its own
    configurations and traffic (an optional ``bench_dir`` key beside the
    manifest); the real manifest has none and uses ``benchmarks/``."""
    with open(manifest_path) as f:
        man = json.load(f)
    here = os.path.dirname(os.path.abspath(manifest_path))
    bench_dir = (os.path.normpath(os.path.join(here, man["bench_dir"]))
                 if "bench_dir" in man else BENCH_DIR)
    c = cell(man, workload)
    rel = next((x["file"] for x in man["configs"] if x["name"] == c["config"]),
               None)
    if rel is None:
        raise MissingPiece(f"configuration {c['config']!r} is not in "
                           f"{os.path.basename(manifest_path)}")
    with open(_need(os.path.join(here, rel),
                    f"configuration {c['config']!r}")) as f:
        config = json.load(f)
    return man, bench_dir, c, config, load_json("traffic", c["traffic"],
                                                bench_dir)


def program_logs_to_stderr() -> None:
    """The program logs to stdout by default; stdout carries JSON only."""
    import sys

    from deepspeed_tpu.utils.logging import logger

    for handler in logger.handlers:
        handler.setStream(sys.stderr)


def metrics_of(manifest: Dict, section: str, workload: str) -> List[Dict]:
    """The metrics of ``section`` that this cell reports: those that list
    it under ``workloads``, and those that list nothing (every cell that
    reports what they move; for ``end_to_end``, every cell)."""
    e2e = {m["name"] for m in metrics_of(manifest, "end_to_end", workload)} \
        if section == "per_layer" else None
    out = []
    for m in manifest[section]:
        if "workloads" in m:
            if workload in m["workloads"]:
                out.append(m)
        elif e2e is None or m["moves"] in e2e:
            out.append(m)
    return out
