#!/usr/bin/env python3
"""Which layer spends the train step: one cell's window with the program's
own tracer (``repro.obs``) attached, its device trace put down to the
program's layer scopes and spans.

  python3 bench/layers.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  Set-up and window are bench/run.py's
(``bench/harness.py``); nothing is checked or compared, and no metric of
BENCHMARK.json is read.  Prints one JSON line:

- ``program``: the tracer's spans in the window, per name the count,
  total and own seconds (less the spans directly inside); its counters
  per step;
- ``long_steps``: the steps over ``LONG_STEP`` x the median, each with
  the seconds of the spans directly inside it and the CPU seconds and
  involuntary context switches the tracer recorded over it; in a traced
  run also the device's busy seconds and busiest operation inside it.

A traced run adds, from the first chip:

- ``scopes``: device own time per ``jit_train_step`` run, by layer scope.
  The trace holds no ``op_name``, so each operation's comes from the
  step's HLO text (``step_op_names``); the operation goes to the
  innermost of ``SCOPES`` on its path, a name counting as a path
  component or wrapped in transforms (``jvp(head)``,
  ``transpose(jvp(ssd))``), else to ``unscoped``.  Each scope also gives
  the part recomputed under ``jax.checkpoint`` (``rematted_computation``
  on the path) and the part XLA's own rematerialisation cloned
  (``.remat`` in the name).  ``coverage`` gives the scoped share of the
  step's device time and the share whose operation has no ``op_name``;
  under ``COVERAGE`` scoped, ``scopes`` is null: the HLO text and the
  executable that ran disagree.
- ``idle_ms``: per program span name, the device's idle time inside that
  name's spans, in ms per step (``all``: every idle gap).  Spans nest,
  so a gap under ``batch`` counts under ``step`` too.

With no TPU, or fewer chips than the cell asks for, it exits with 3.
"""

from __future__ import annotations

import bisect
import json
import os
import re
import shutil
import statistics
import sys
import tempfile
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROGRAM = "repro."
PROGRAM_STEP = "train"
TRAIN_STEP = "jit_train_step"
SCOPES = ("layers", "mamba", "ssd", "shared_block", "attn", "mlp", "embed", "head", "optimizer")
UNSCOPED = "unscoped"
CHECKPOINT_RECOMPUTE = "rematted_computation"
XLA_REMAT = ".remat"
COVERAGE = 0.9
_UNWRAP = re.compile(r"^(?:[\w-]+\()*([^()]*)\)*$")
_HLO_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = ")
_HLO_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_HLO_CALLS = re.compile(r"(?:calls|body|condition|to_apply)=%([\w.\-]+)|branch_computations=\{([^}]*)\}")


def op_names_from_hlo(text: str) -> Dict[str, str]:
    """HLO instruction name -> ``op_name`` metadata, from a compiled
    module's text (``jax.stages.Compiled.as_text()``).  An instruction the
    compiler made without metadata (a layout copy, a bitcast fusion) takes
    the ``op_name`` of the instruction that calls its computation: inside
    a loop body, the loop's."""
    own: Dict[str, Optional[str]] = {}
    comp_of: Dict[str, str] = {}
    caller: Dict[str, str] = {}
    comp = ""
    for line in text.splitlines():
        m = _HLO_INSTR.match(line)
        if m is None:
            if line.strip() == "}":
                comp = ""
            elif line.rstrip().endswith("{"):
                comp = line.split("(", 1)[0].replace("ENTRY", "").strip().lstrip("%")
            continue
        name = m.group(1)
        meta = _HLO_OP_NAME.search(line)
        own[name] = meta.group(1) if meta else None
        comp_of[name] = comp
        for one, many in _HLO_CALLS.findall(line):
            for c in [one] if one else many.split(","):
                caller[c.strip().lstrip("%")] = name

    def resolve(name: str, seen: int = 0) -> Optional[str]:
        if own.get(name) is not None or seen > 64:
            return own.get(name)
        up = caller.get(comp_of.get(name, ""))
        return resolve(up, seen + 1) if up else None

    return {n: p for n in own if (p := resolve(n)) is not None}


def scope_of(path: str) -> str:
    found = UNSCOPED
    for part in path.split("/"):
        core = _UNWRAP.sub(r"\1", part)
        if core in SCOPES:
            found = core
    return found


def step_op_names(trainer) -> Dict[str, str]:
    """HLO instruction name -> ``op_name`` of the trainer's compiled step,
    lowered again from its state and a batch placed as its steps place
    theirs, and compiled afresh with the persistent cache off and the
    process's caches cleared: metadata is not in the cache key, so the
    executable set-up loaded can carry the ``op_name`` of an older build
    of the program.  The instructions themselves are the same."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    cls = type(trainer)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    try:
        text = cls._step_jit(trainer).lower(trainer.state, cls._batch(trainer)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()
    return op_names_from_hlo(text)


def _overlap(a: List[Tuple[float, float]], b: List[Tuple[float, float]]) -> float:
    """Length of the intersection of two sorted lists of disjoint intervals."""
    out, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        out += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def reduce(trace: Dict[str, Any]) -> Dict[str, Any]:
    """``scopes``, ``coverage``, ``idle_ms`` and ``device_steps`` (per
    program step in the window, in order) from a trace in bench/trace.py's
    plain form with ``op_names`` added; {} where it holds no TPU."""
    from bench.trace import DEVICE, WINDOW, _clip, _union

    win, prog, chips = None, [], {}
    for p in trace["planes"]:
        m = DEVICE.match(p["name"])
        if m:
            chips[int(m.group(1))] = {ln["name"]: ln["events"] for ln in p["lines"]}
        elif p["name"].startswith("/host:"):
            for ln in p["lines"]:
                for n, s, d in ln["events"]:
                    if n == WINDOW:
                        win = (s, s + d)
                    elif n.startswith(PROGRAM) or n == PROGRAM_STEP:
                        prog.append(("step" if n == PROGRAM_STEP else n[len(PROGRAM):], s, s + d))
    if win is None:
        raise ValueError(f"no {WINDOW} span in the trace")
    if not chips:
        return {}
    lo, hi = win
    dev = chips[min(chips)]
    ops = sorted(dev.get("XLA Ops", []), key=lambda e: e[1])
    starts = [e[1] for e in ops]
    in_span = lambda s, e: ops[bisect.bisect_left(starts, s) : bisect.bisect_left(starts, e)]  # noqa: E731
    busy = _union(_clip([(s, s + d) for _, s, d in ops], lo, hi))
    gaps = [(a, b) for a, b in zip([lo] + [e for _, e in busy], [s for s, _ in busy] + [hi]) if b > a]
    steps = sorted((s, e) for n, s, e in prog if n == "step" and lo <= s < hi)
    runs = [(s, s + d) for n, s, d in dev.get("XLA Modules", []) if n.startswith(TRAIN_STEP) and lo <= s < hi]
    scopes, coverage = _scopes(trace.get("op_names", {}), in_span, runs)
    by_name: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    for n, s, e in prog:
        by_name[n].append((s, e))
    idle = {n: _overlap(gaps, _union(iv)) for n, iv in by_name.items()}
    idle["all"] = sum(b - a for a, b in gaps)
    per_step = 1e-6 / max(len(steps), 1)
    return {
        "scopes": scopes if coverage.get("scoped", 0.0) >= COVERAGE else None,
        "coverage": coverage,
        "idle_ms": {n: v * per_step for n, v in sorted(idle.items(), key=lambda kv: -kv[1])},
        "device_steps": [_step_device(in_span(s, e), s, e) for s, e in steps],
    }


def _scopes(op_names, in_span, runs) -> Tuple[Dict[str, Dict[str, float]], Dict[str, float]]:
    from bench.trace import self_times

    total: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0.0, 0.0])
    missing = 0.0
    for s, e in runs:
        for op, t in self_times(in_span(s, e), s, e).items():
            path = op_names.get(op)
            missing += t if path is None else 0.0
            acc = total[scope_of(path or "")]
            acc[0] += t
            acc[1] += t if path and CHECKPOINT_RECOMPUTE in path else 0.0
            acc[2] += t if XLA_REMAT in op else 0.0
    whole = sum(a[0] for a in total.values())
    if not whole:
        return {}, {}
    ms = 1e-6 / len(runs)
    scopes = {
        k: {"ms": a * ms, "recompute_ms": b * ms, "xla_remat_ms": c * ms}
        for k, (a, b, c) in sorted(total.items(), key=lambda kv: -kv[1][0])
    }
    coverage = {"scoped": 1.0 - total[UNSCOPED][0] / whole, "no_op_name": missing / whole}
    return scopes, coverage


def _step_device(inside, s: float, e: float) -> Dict[str, Any]:
    from bench.trace import _clip, _union, self_times

    own = self_times(inside, s, e)
    name = max(own, key=own.get) if own else None
    return {
        "busy_s": sum(b - a for a, b in _union(_clip([(a, a + d) for _, a, d in inside], s, e))) / 1e9,
        "longest_op": [name, own[name] / 1e9] if name else None,
    }


def program_summary(tracer, lo: float, hi: float) -> Dict[str, Any]:
    """The tracer's spans inside [lo, hi]: per name the count, total and
    own seconds; and its counters over the steps inside, per step."""
    recs = [(r, k) for r, k in zip(tracer.records, tracer.steps_of) if lo <= r[1] and r[2] <= hi]
    spans: Dict[str, Dict[str, float]] = {}
    for (n, t0, t1, depth), _ in recs:
        inner = sum(c1 - c0 for (_, c0, c1, cd), _ in recs if cd == depth + 1 and t0 <= c0 and c1 <= t1)
        s = spans.setdefault(n, {"count": 0, "total_s": 0.0, "self_s": 0.0})
        s["count"] += 1
        s["total_s"] += t1 - t0
        s["self_s"] += t1 - t0 - inner
    steps = sorted({k for (n, *_), k in recs if n == "step"})
    counters: Dict[str, Dict[int, int]] = defaultdict(dict)
    for k in steps:
        for name, v in tracer.counters.get(k, {}).items():
            counters[name][k] = v
    return {
        "spans": spans,
        "steps": len(steps),
        "counters": {
            name: {"total": sum(by.values()), "per_step": sum(by.values()) / len(steps), "by_step": by}
            for name, by in counters.items()
        },
    }


def long_steps(tracer, lo: float, hi: float, device: Optional[List[Dict[str, Any]]] = None, most: int = 10):
    """The tracer's steps inside [lo, hi] over LONG_STEP x their median,
    longest first, each with what the tracer recorded of it and, given
    ``device`` (per step in the window, in order), the device's part."""
    from bench.harness import LONG_STEP

    recs = list(zip(tracer.records, tracer.steps_of))
    steps = [(t0, t1, k) for (n, t0, t1, _), k in recs if n == "step" and lo <= t0 and t1 <= hi]
    if not steps:
        return []
    med = statistics.median(t1 - t0 for t0, t1, _ in steps)
    out = []
    for i, (t0, t1, k) in enumerate(steps):
        if t1 - t0 <= LONG_STEP * med:
            continue
        inside: Dict[str, float] = defaultdict(float)
        for (n, c0, c1, depth), kk in recs:
            if kk == k and depth == 1 and t0 <= c0 and c1 <= t1:
                inside[n] += c1 - c0
        entry = {"step": k, "seconds": t1 - t0, "spans": dict(inside), **tracer.step_attrs[k]}
        if device is not None and i < len(device):
            entry["device"] = device[i]
        out.append(entry)
    return sorted(out, key=lambda x: -x["seconds"])[:most]


def run(name: str, seed: int, seconds: float, trace: bool, *, root=None, require_chip: bool = True) -> Dict[str, Any]:
    from pathlib import Path

    import jax

    from bench import trace as trace_mod
    from bench.harness import Cell, NoChip, device_info, enable_cache
    from bench.spans import Spans
    from repro.obs import Tracer

    root = Path(root or ROOT)
    cell = Cell(name, root)
    if require_chip:
        enable_cache(jax, root)
    dev = device_info(jax)
    if require_chip and (dev["platform"] != "tpu" or dev["count"] < cell.chips):
        raise NoChip(f"{name} needs {cell.chips} TPU chip(s); JAX found {dev['count']} x {dev['platform']}")
    spans = Spans(annotate=trace)
    r = cell.driver.Run(cell, seed)
    tdir = tempfile.mkdtemp(prefix="bench_layers_") if trace else None
    try:
        r.setup()
        tracer = r.trainer.tracer = Tracer(annotate=trace)
        if trace:
            jax.profiler.start_trace(tdir)
        with spans.span("window"):
            window = r.window(seconds)
        if trace:
            jax.profiler.stop_trace()
        _, lo, hi, _ = spans.records[-1]
        out: Dict[str, Any] = {
            "workload": name, "seed": seed, "device": dev, "steps": window["steps"],
            "train_tokens_per_s": window["end_to_end"]["train_tokens_per_s"],
            "program": program_summary(tracer, lo, hi),
        }
        device_steps = None
        if trace:
            t = trace_mod.load(tdir)
            t["op_names"] = step_op_names(r.trainer)
            reduced = reduce(t)
            device_steps = reduced.pop("device_steps", None)
            out.update(reduced)
        out["long_steps"] = long_steps(tracer, lo, hi, device_steps)
        return out
    finally:
        r.free()
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)


def main() -> int:
    import argparse

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args()

    from bench.harness import NoChip

    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
