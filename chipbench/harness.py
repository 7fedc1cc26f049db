"""What every cell shares: finding its files by name, host spans, and the
comparison that decides ``correct``.

Everything that belongs to one configuration, traffic mix, driver or metric
lives in a file of its own under this directory and is found by the name
``BENCHMARK.json`` gives it:

- ``configs/<config>.json``: the configuration as it is run;
- ``traffic/<traffic>.json``: the job (driver, layout, optimizer, events);
- ``drivers/<driver>.py``: drives one entry of the program;
- ``metrics/<metric>.py``: reads one metric (``read(ctx)``);
- ``reference/<reference>.py``: the plain reference of a model family;
- ``limits/<workload>.json``: the limits of the numbers compared.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import time
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional, Tuple

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path) -> ModuleType:
    """Import a file by path (names may hold dots, as metric names do)."""
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Bench:
    """The benchmark's files, rooted at ``bench_dir``, and its manifest."""
    manifest: dict
    bench_dir: Path = BENCH_DIR

    @classmethod
    def load(cls) -> "Bench":
        return cls(load_json(ROOT / "BENCHMARK.json"))

    def workload(self, name: str) -> dict:
        for w in self.manifest["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        return load_json(self.bench_dir / "configs" / f"{name}.json")

    def traffic(self, name: str) -> dict:
        return load_json(self.bench_dir / "traffic" / f"{name}.json")

    def limits(self, workload: str) -> dict:
        return load_json(self.bench_dir / "limits" / f"{workload}.json")

    def driver(self, name: str) -> ModuleType:
        return load_module(self.bench_dir / "drivers" / f"{name}.py")

    def reference(self, name: str) -> ModuleType:
        return load_module(self.bench_dir / "reference" / f"{name}.py")

    def reader(self, metric: str) -> ModuleType:
        return load_module(self.bench_dir / "metrics" / f"{metric}.py")

    def metrics_for(self, workload: str, traced: bool) -> List[dict]:
        """The cell's end-to-end metrics (untraced) or per-layer ones."""
        group = self.manifest["per_layer" if traced else "end_to_end"]
        return [m for m in group
                if workload in m.get("workloads", [workload])]


# ---------------------------------------------------------------------------
# configurations
# ---------------------------------------------------------------------------
def model_config(spec: dict):
    """The program's ``ModelConfig`` for a configuration file: the registry
    entry ``registry_id`` with the keys in ``reduced`` replaced, and those
    in ``registry_departs`` (where the registry's value is not the
    source's) set to the file's value.  Every other key that ``fields``
    maps must equal the registry's value, so a changed published width is
    refused, never measured."""
    from repro import configs
    cfg = configs.get_config(spec["registry_id"])
    departs = spec.get("registry_departs", {})
    changes, wrong = {}, []
    for key, field in spec["fields"].items():
        have = getattr(cfg, field)
        if key in spec["reduced"]:
            if spec["reduced"][key] != have:
                wrong.append(f"{key}: reduced from {spec['reduced'][key]!r} "
                             f"but the registry has {have!r}")
            changes[field] = spec[key]
        elif key in departs:
            changes[field] = spec[key]
        elif spec[key] != have:
            wrong.append(f"{key}: file {spec[key]!r} != registry {have!r}")
    if wrong:
        raise ValueError(f"configuration {spec.get('name')} disagrees with "
                         f"the registry entry {spec['registry_id']}: "
                         + "; ".join(wrong))
    return dataclasses.replace(cfg, **changes)


# ---------------------------------------------------------------------------
# spans: host intervals around each call into the program
# ---------------------------------------------------------------------------
class Spans:
    """Host spans on ``time.perf_counter``; when ``annotate`` is on each is
    also a ``jax.profiler.TraceAnnotation``, so the profiler's trace holds
    it on the device's clock."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.items: List[Tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        ann = contextlib.nullcontext()
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(f"chipbench.{name}")
        with ann:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.items.append((name, t0, time.perf_counter()))


# ---------------------------------------------------------------------------
# correct: the program's readings against the reference's
# ---------------------------------------------------------------------------
#: A leaf whose reference gradient is under this share of the median leaf's
#: moves under Adam by round-off alone; it is left out of the change.
FROZEN_SHARE = 1e-3


def _median(xs: List[float]) -> float:
    s = sorted(xs)
    n = len(s)
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
              leaves: Optional[List[str]] = None) -> Dict[str, float]:
    """Each leaf's gap between the program's norm and the reference's, over
    the larger of that leaf's reference norm and the median leaf's."""
    if set(prog) != set(ref):
        raise ValueError(f"leaves differ: program only {sorted(set(prog) - set(ref))}, "
                         f"reference only {sorted(set(ref) - set(prog))}")
    names = sorted(leaves if leaves is not None else ref)
    med = _median([ref[n] for n in names])
    gaps = {n: abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30) for n in names}
    return {n: (g if math.isfinite(g) else math.inf) for n, g in gaps.items()}


def worst(gaps: Dict[str, float]) -> Tuple[float, str]:
    at = max(gaps, key=gaps.get)
    return gaps[at], at


def readings(prog: dict, ref: dict) -> Dict[str, dict]:
    """The numbers a cell may compare.  ``prog`` and ``ref`` each hold
    ``losses`` (one per step compared), ``grad_norms`` (per leaf, of the
    first step's gradient) and ``change_norms`` (per leaf, of the master
    weights' change over the steps compared); ``ref`` also holds
    ``grad_diff_norms`` (the norm of each leaf's difference between the two
    first gradients).  ``grad_diff_gap`` is its median leaf's, over the
    larger of that leaf's reference norm and the median leaf's: a norm's gap
    hides rounding that is random element by element, a difference's norm
    does not."""
    pl, rl = prog["losses"], ref["losses"]
    if len(pl) != len(rl) or not pl:
        raise ValueError(f"{len(pl)} program losses against {len(rl)}")
    loss = max((abs(p - r) / abs(r) if math.isfinite(p) else math.inf)
               for p, r in zip(pl, rl))
    grads = leaf_gaps(prog["grad_norms"], ref["grad_norms"])
    grad, grad_at = worst(grads)
    med = _median(list(ref["grad_norms"].values()))
    moving = [n for n, g in ref["grad_norms"].items()
              if g >= FROZEN_SHARE * med]
    change, change_at = worst(leaf_gaps(prog["change_norms"],
                                        ref["change_norms"], moving))
    diff = _median([d / max(ref["grad_norms"][n], med, 1e-30)
                    for n, d in ref["grad_diff_norms"].items()])
    return {"loss_gap": {"value": loss, "steps": len(pl)},
            "grad_gap": {"value": grad, "leaf": grad_at},
            "grad_diff_gap": {"value": diff if math.isfinite(diff) else math.inf},
            "change_gap": {"value": change, "leaf": change_at,
                           "frozen": sorted(set(ref["grad_norms"]) - set(moving))}}


def judge(numbers: Dict[str, dict], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, dict]]:
    """The numbers that the cell's limits name, each beside its limit."""
    checks = {name: {"value": numbers[name]["value"], "limit": lim}
              for name, lim in limits.items()}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
