"""Shared pieces of the chip benchmark.

Everything a cell needs is found by name: ``workloads/<cell>.json`` names
its configuration (``configs/<config>.json`` with the plain reference
``configs/<config>.py`` beside it) and its traffic mix
(``traffic/<mix>.json``); each per-layer metric is read by
``metrics/<metric>.py`` (or ``metrics/<stem>.py`` for ``<stem>.<suffix>``).
This module loads those files, checks the device, clocks compilation, and
prints the result line.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import sys
import time
from pathlib import Path

PROCESS_T0 = time.monotonic()

ROOT = Path(__file__).resolve().parent
REPO = ROOT.parents[1]
if str(REPO / "src") not in sys.path:
    sys.path.insert(0, str(REPO / "src"))


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(rel: str) -> dict:
    return json.loads((ROOT / rel).read_text())


def load_module(path: Path):
    """Import a file by path (names may hold '-' and '.')."""
    name = "chipbench_" + "".join(c if c.isalnum() else "_"
                                  for c in str(path.relative_to(ROOT)))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def cell(name: str) -> dict:
    return load_json(f"workloads/{name}.json")


def config_file(name: str) -> dict:
    return load_json(f"configs/{name}.json")


def reference(name: str):
    return load_module(ROOT / "configs" / f"{name}.py")


def traffic_mix(name: str) -> dict:
    return load_json(f"traffic/{name}.json")


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file: the
    registry entry with every mapped key set to the file's value."""
    from repro.configs import get_config

    base = get_config(conf["registry"])
    fields = {f: conf["config"][k] for k, f in conf["program_fields"].items()}
    return dataclasses.replace(base, **fields)


def metric_reader(name: str):
    """``metrics/<name>.py``, else ``metrics/<stem>.py`` for a suffixed
    name such as ``decode_tick_ms.tput``."""
    path = ROOT / "metrics" / f"{name}.py"
    if not path.exists():
        path = ROOT / "metrics" / f"{name.split('.')[0]}.py"
    return load_module(path)


def peaks(device_kind: str) -> dict:
    table = load_json("peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json; add them with their source")
    return table[device_kind]


def require_chips(n: int):
    """The devices of a cell on ``n`` chips; ``NoChip`` where JAX finds no
    TPU or fewer than ``n`` chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform})")
    if len(devs) < n:
        raise NoChip(f"cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def device_info(devs) -> dict:
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest of ``devs`` (0 where the backend
    does not report it)."""
    out = 0
    for d in devs:
        stats = d.memory_stats() or {}
        out = max(out, int(stats.get("peak_bytes_in_use", 0)))
    return out


class CompileClock:
    """Seconds and count of JAX backend compilations (persistent-cache
    reads included) since construction."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1


def enable_cache() -> str:
    """The program's persistent compilation cache (``.jax_cache/`` in the
    checkout unless ``JAX_COMPILATION_CACHE_DIR`` says otherwise), with
    every program cached however quick its compile, so a warm run
    compiles nothing."""
    import jax

    from repro.compile_cache import enable_compile_cache

    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def split_seed(seed: int) -> tuple[int, int]:
    """Two 31-bit seeds from any whole number, however many bits it
    takes: one for JAX keys, one for numpy."""
    import numpy as np

    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return int(words[0] & 0x7FFFFFFF), int(words[1] & 0x7FFFFFFF)


def nearest_rank(xs, q: float) -> float:
    """Nearest-rank percentile ``q`` (0-100) of a non-empty sample."""
    xs = sorted(float(x) for x in xs)
    return xs[max(math.ceil(q / 100.0 * len(xs)) - 1, 0)]


def summary(xs) -> dict:
    if not xs:
        return {"count": 0}
    return {"count": len(xs), "p50": nearest_rank(xs, 50),
            "p95": nearest_rank(xs, 95), "max": max(xs)}


def emit(result: dict) -> None:
    """Print the compared numbers on standard error, then the result as
    the last line of standard output, with ``checks`` as its last key."""
    checks = result.pop("checks")
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    result["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def start_trace(trace_dir) -> None:
    """Start the profiler with host annotations and without Python
    function tracing (which would slow the host loop it measures)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


def span(tracing: bool, name: str):
    """A host span in the profiler's trace while tracing, else nothing."""
    if not tracing:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)
