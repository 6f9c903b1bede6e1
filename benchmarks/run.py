"""Run every benchmark; print one CSV (name,metrics...).

  PYTHONPATH=src python -m benchmarks.run [--full]

--full restores the paper's 1:1 experiment durations (10x slower).
"""

from __future__ import annotations

import argparse
import os
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale durations")
    args = ap.parse_args()
    if args.full:
        os.environ["BENCH_SCALE"] = "1.0"

    # import AFTER the env var so common.SCALE picks it up
    from benchmarks import (
        bench_ablation,
        bench_batching,
        bench_fast_paxos,
        bench_horizontal,
        bench_leader_failure,
        bench_matchmaker_reconfig,
        bench_nemesis,
        bench_proc,
        bench_reconfiguration,
        bench_sharding,
        bench_thriftiness,
        bench_wire,
        common,
    )

    suites = [
        ("fig9/table1 reconfiguration", bench_reconfiguration.main),
        ("fig10 horizontal baseline", bench_horizontal.main),
        ("fig17 ablation (WAN)", bench_ablation.main),
        ("fig19/20 failures", bench_leader_failure.main),
        ("fig21/table2 matchmaker reconfig", bench_matchmaker_reconfig.main),
        ("sec7 fast paxos", bench_fast_paxos.main),
        ("fig14 thriftiness", bench_thriftiness.main),
        ("sec8 hot-path batching", bench_batching.main),
        ("wire plane codec + tcp", bench_wire.main),
        ("sharded log plane", bench_sharding.main),
        ("sec8 reconfiguration under fire", bench_nemesis.main),
        ("process plane (one OS process per node)", lambda: bench_proc.main(fast=True)),
    ]
    for name, fn in suites:
        t0 = time.time()
        print(f"== {name} ==", file=sys.stderr)
        fn(fast=not args.full)
        print(f"   ({time.time() - t0:.1f}s)", file=sys.stderr)

    print()
    common.emit_csv()


if __name__ == "__main__":
    main()
