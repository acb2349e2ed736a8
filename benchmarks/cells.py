"""Finding a cell's files by the names in BENCHMARK.json, and handing a
configuration to the program.

Nothing here knows a cell, a configuration, a mix or a metric by name: a
workload entry names `benchmarks/configs/<config>.json` and
`benchmarks/traffic/<traffic>.json`, a per-layer metric names
`benchmarks/layer_metrics/<name>.py`, a configuration names its plain
reference `benchmarks/references/<reference>.py` and, where its block is not
the dense decoder `benchmarks/flops.py` counts, its own count of model FLOPs
`benchmarks/model_flops/<flops>.py`. A later PR adds files and entries and
edits nothing.

A configuration reaches the program through `models/registry.register`, the
program's own seam for a model family: one family per configuration file,
whose `config_fn` hands the file's `program.fields` to the program's own
constructor (`program.config_fn`, e.g. `llama_config`). That carries every
published key (Qwen2.5's QKV bias and rope_theta, which no CLI flag sets)
without an edit to the program.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Mapping, Tuple

from benchmarks import flops

MANIFEST = "BENCHMARK.json"
# what tp x dp under ZeRO-2 must show in the step; a mix of another layout
# states its own under `collectives`
DEFAULT_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter")
SEED_MODULUS = 2**31  # the trainer's numpy streams take seeds below 2**32


class CellError(ValueError):
    """The manifest or one of a cell's files is missing or inconsistent."""


def load_json(root: str, relpath: str) -> Any:
    path = os.path.join(root, relpath)
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise CellError("no file %s" % path) from None
    except json.JSONDecodeError as e:
        raise CellError("%s is not JSON: %s" % (path, e)) from None


@dataclass(frozen=True)
class Cell:
    root: str
    workload: Dict[str, Any]  # the entry of BENCHMARK.json's `workloads`
    config: Dict[str, Any]  # benchmarks/configs/<config>.json
    traffic: Dict[str, Any]  # benchmarks/traffic/<traffic>.json
    manifest: Dict[str, Any]

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    @property
    def fields(self) -> Dict[str, Any]:
        return config_fields(self.config)

    @property
    def tokens_a_step(self) -> int:
        return int(self.traffic["global_batch"]) * int(self.traffic["seq_length"])

    @property
    def collectives(self) -> Tuple[str, ...]:
        """The collectives this mix's layout must show in the step's HLO."""
        return tuple(self.traffic.get("collectives", DEFAULT_COLLECTIVES))

    def metrics(self, group: str) -> List[Dict[str, Any]]:
        """The manifest's `end_to_end` or `per_layer` metrics this cell reports."""
        return [m for m in self.manifest[group]
                if "workloads" not in m or self.name in m["workloads"]]


def load_cell(root: str, workload: str) -> Cell:
    manifest = load_json(root, MANIFEST)
    entries = [w for w in manifest["workloads"] if w["name"] == workload]
    if len(entries) != 1:
        raise CellError("workload %r is not in %s (it has: %s)" % (
            workload, MANIFEST, ", ".join(w["name"] for w in manifest["workloads"])))
    entry = entries[0]
    configs = [c for c in manifest["configs"] if c["name"] == entry["config"]]
    if len(configs) != 1:
        raise CellError("configuration %r is not in %s" % (entry["config"], MANIFEST))
    config = load_json(root, configs[0]["file"])
    traffic = load_json(root, "benchmarks/traffic/%s.json" % entry["traffic"])
    if int(traffic["chips"]) != int(entry["chips"]):
        raise CellError("traffic %r lays out %s chips, workload %r asks for %s" % (
            entry["traffic"], traffic["chips"], workload, entry["chips"]))
    return Cell(root, entry, config, traffic, manifest)


def config_fields(config: Mapping) -> Dict[str, Any]:
    """The program's config fields: `program.fields`, where a string "$key"
    stands for the file's own top-level (published) key."""
    out = {}
    for field, value in config["program"]["fields"].items():
        if isinstance(value, str) and value.startswith("$"):
            if value[1:] not in config:
                raise CellError("program.fields.%s names %s, which the file lacks"
                                % (field, value))
            value = config[value[1:]]
        out[field] = value
    return out


def flops_a_token(cell: Cell) -> float:
    """Forward + backward model FLOPs a token of this cell, in the convention
    of `benchmarks/flops.py`: from the module the configuration names under
    `flops` (`benchmarks/model_flops/<name>.py`), else from `flops.py`."""
    name = cell.config.get("flops")
    module = flops if name is None else load_module(
        cell.root, "benchmarks/model_flops/%s.py" % name)
    return float(module.train_flops_a_token(cell.fields, int(cell.traffic["seq_length"])))


def import_attr(spec: str) -> Callable:
    module, _, attr = spec.partition(":")
    return getattr(importlib.import_module(module), attr)


def family_name(cell: Cell) -> str:
    return "bench:" + cell.workload["config"]


def register_family(cell: Cell):
    """One registered model family per configuration file."""
    from galvatron_tpu.models.registry import ModelFamily, register

    program = cell.config["program"]
    build = import_attr(program["config_fn"])
    fields = cell.fields
    name = family_name(cell)

    def config_fn(size, **overrides):
        # overrides are the trainer's own: max_seq_len (--seq_length) and
        # compute_dtype (--mixed_precision)
        return build(program["preset"], **{**fields, **overrides})

    return register(ModelFamily(
        name=name, config_fn=config_fn, meta_configs={name: {}}, default_size=name))


def train_argv(cell: Cell, seed: int, trace_dir=None, trace_steps=None) -> List[str]:
    """The flags of `python -m galvatron_tpu.cli train` for this cell."""
    t = cell.traffic
    argv = [
        "--model_type", family_name(cell),
        "--set_seqlen_manually", "1", "--seq_length", str(t["seq_length"]),
        "--mixed_precision", "bf16",
        "--global_train_batch_size", str(t["global_batch"]),
        # the window ends the run (WindowClock sets train_iters); nothing is
        # logged, evaluated or saved inside it
        "--train_iters", "1000000", "--log_interval", "1000000",
        "--seed", str(seed % SEED_MODULUS),
        *[str(f) for f in t["train_flags"]],
    ]
    if trace_dir is not None:
        argv += ["--xla_trace", trace_dir, "--trace_steps", "%d:%d" % trace_steps]
    return argv


def load_module(root: str, relpath: str):
    """A metric reader or a plain reference, by its path under the root."""
    path = os.path.join(root, relpath)
    if not os.path.exists(path):
        raise CellError("no file %s" % path)
    name = "bench_" + relpath.replace(os.sep, "_").replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
