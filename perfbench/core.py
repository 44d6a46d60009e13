"""What every cell shares: finding a cell's files by name, the device check,
the compile counter, the benchmark's host spans, and the result line.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix. The
configuration's file is the one its ``configs`` entry names; the traffic mix
is ``traffic/<traffic>.json``, whose ``loop`` key names the module
``loops/<loop>.py`` that runs it; each per-layer metric is read by
``metrics/<metric name>.py``. Adding a cell, a configuration, a traffic mix
or a metric is adding files and entries; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import sys
from pathlib import Path
from types import ModuleType

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    name: str
    entry: dict  # the workloads entry
    config: dict  # the configuration as run
    traffic: dict  # the traffic mix's parameters
    loop: ModuleType  # the traffic's load loop: set-up, window, check
    end_to_end: list  # metric entries this cell reports with --trace 0
    per_layer: list  # metric entries this cell reports with --trace 1
    readers: dict  # per-layer metric name -> reader module


def load_module(path: Path) -> ModuleType:
    """Import a file of the benchmark by path (metric names hold dots)."""
    name = f"perfbench.{path.parent.name}.{path.stem}"
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module  # dataclasses look their module up by name
    spec.loader.exec_module(module)
    return module


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(spec: dict, workload: str, root: Path = ROOT) -> Cell:
    """Everything one cell needs, found by the names in ``spec``."""
    bench = root / spec["paths"][0]
    entries = {w["name"]: w for w in spec["workloads"]}
    if workload not in entries:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    entry = entries[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[entry["config"]]["file"]).read_text())
    traffic = json.loads((bench / "traffic" / f"{entry['traffic']}.json").read_text())
    loop = load_module(bench / "loops" / f"{traffic['loop']}.py")
    per_layer = [m for m in spec["per_layer"] if _applies(m, workload)]
    readers = {m["name"]: load_module(bench / "metrics" / f"{m['name']}.py") for m in per_layer}
    return Cell(
        name=workload, entry=entry, config=config, traffic=traffic, loop=loop,
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=per_layer, readers=readers,
    )


def check_devices(chips: int) -> list:
    """The devices to run on; exits when JAX finds no TPU or too few chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"perfbench: needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        sys.exit(f"perfbench: the cell asks for {chips} chips, JAX found {len(devices)}")
    return devices


class CompileCounter:
    """Counts XLA backend compiles (and persistent-cache loads) as JAX
    reports them through ``jax.monitoring``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


class Spans:
    """Host spans around calls into the program's layers, recorded into the
    profiler's trace as ``bench.<name>``. Installed for the traced run only:
    each ``(module, attribute, name)`` is wrapped in place and put back by
    ``remove()``. ``record`` names spans whose calls' arguments are kept."""

    def __init__(self):
        self._saved: list = []
        self.calls: dict[str, list] = {}

    def install(self, targets, record=()) -> None:
        import jax

        for module_name, attr, name in targets:
            module = importlib.import_module(module_name)
            fn = getattr(module, attr)
            keep = self.calls.setdefault(name, []) if name in record else None

            def wrapped(*args, __fn=fn, __label="bench." + name, __keep=keep, **kwargs):
                with jax.profiler.TraceAnnotation(__label):
                    out = __fn(*args, **kwargs)
                if __keep is not None:
                    __keep.append((args, kwargs))
                return out

            setattr(module, attr, wrapped)
            self._saved.append((module, attr, fn))

    def remove(self) -> None:
        for module, attr, fn in reversed(self._saved):
            setattr(module, attr, fn)
        self._saved.clear()


def span(name: str):
    """A benchmark span of its own (no program call inside it is wrapped)."""
    import jax

    return jax.profiler.TraceAnnotation("bench." + name)


@dataclasses.dataclass
class Check:
    """One number compared with the plain reference, and its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit

