#!/usr/bin/env python
"""Standalone whole-program verifier CLI (analysis/verifier.py).

Verify a serialized Program (Program.to_json output, e.g. a checkpointed
model or a transpiler artifact) without executing it — the same passes
PT_VERIFY=1 runs inside the executor, plus the sanity check of a gconv
autotune cache:

    python tools/verify_program.py program.json
    python tools/verify_program.py program.json --mesh dp=2,tp=4 \
        --fetch mean_0 --feed data --feed label
    python tools/verify_program.py --autotune-cache ~/.cache/paddle_tpu/gconv_autotune.json

The collective-audit pass needs a mesh AND derived placements — before
this CLI grew --builder/--transpile/--plan it only ever fired inside
executor pre-passes. Now it runs standalone on a transpiled clone:

    # sharding pass on a clone of the builder's transformer, then ALL
    # passes incl. collective-audit against the mesh
    python tools/verify_program.py --builder transformer \
        --mesh dp=2,sp=2,tp=2 --transpile
    # apply a planner artifact instead of deriving (mesh comes from
    # the plan)
    python tools/verify_program.py --builder transformer --plan plan.json
    python tools/verify_program.py program.json --plan plan.json

Exit status: 0 clean (warnings allowed), 1 any error-severity finding,
2 usage/IO problems.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def parse_mesh(spec: str) -> dict:
    axes = {}
    for part in spec.split(","):
        if not part:
            continue
        name, _, size = part.partition("=")
        if not size:
            raise argparse.ArgumentTypeError(
                f"mesh axis {part!r} is not name=size")
        axes[name.strip()] = int(size)
    return axes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("program", nargs="?",
                    help="Program JSON file (Program.to_json)")
    ap.add_argument("--mesh", type=parse_mesh, default=None,
                    help="mesh axes as name=size,name=size — enables "
                         "concrete shard-divisibility checks")
    ap.add_argument("--feed", action="append", default=[],
                    help="a var name that will be fed (repeatable)")
    ap.add_argument("--fetch", action="append", default=[],
                    help="a var name that will be fetched (repeatable)")
    ap.add_argument("--passes", default=None,
                    help="comma-separated subset of verifier passes")
    ap.add_argument("--builder", default=None,
                    choices=["resnet", "transformer", "decode"],
                    help="build this program (tools/cost_report.py "
                         "builders) instead of loading a program JSON")
    ap.add_argument("--pp", type=int, default=0,
                    help="pipeline-transpile the transformer builder "
                         "into this many stages (needed to verify a pp "
                         "plan: the plan re-stages the program's own "
                         "pipeline op)")
    ap.add_argument("--microbatches", type=int, default=4,
                    help="microbatch count for --pp (default 4)")
    ap.add_argument("--transpile", action="store_true",
                    help="run the sharding transpiler on a clone before "
                         "verifying (requires --mesh) — makes the "
                         "collective-audit pass runnable standalone")
    ap.add_argument("--plan", default=None, metavar="PLAN_JSON",
                    help="apply a planner artifact (tools/plan.py) to a "
                         "clone before verifying; the mesh defaults to "
                         "the plan's axes")
    ap.add_argument("--autotune-cache", default=None,
                    help="validate a gconv autotune cache JSON")
    args = ap.parse_args(argv)

    if not (args.program or args.builder or args.autotune_cache):
        ap.error("nothing to do: give a program JSON, --builder, or "
                 "--autotune-cache")
    if args.transpile and args.plan:
        ap.error("--transpile and --plan are mutually exclusive: a plan "
                 "records its placements, nothing is left to derive")
    if args.transpile and args.mesh is None:
        ap.error("--transpile needs --mesh (the axes the sharding pass "
                 "derives placements for)")

    rc = 0

    if args.autotune_cache:
        from paddle_tpu.analysis import artifacts
        path = args.autotune_cache
        try:
            with open(os.path.expanduser(path)) as f:
                doc = json.load(f)
        except (OSError, ValueError) as e:
            print(f"{path}: cannot load: {e}", file=sys.stderr)
            return 2
        problems = artifacts.validate_autotune_cache(doc)
        for p in problems:
            print(f"{path}: error[artifact-sanity] {p}")
        if problems:
            rc = 1
        else:
            print(f"{path}: artifact verifies clean")

    if args.program or args.builder:
        from paddle_tpu.analysis import verify_program
        from paddle_tpu.core.program import Program
        if args.builder:
            sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
            from cost_report import BUILDERS
            if args.pp > 1:
                if args.builder != "transformer":
                    ap.error("--pp needs the transformer builder's "
                             "repeated layer region")
                program, _startup = BUILDERS[args.builder](
                    True, pp=args.pp, microbatches=args.microbatches)
            else:
                program, _startup = BUILDERS[args.builder](True)
        else:
            try:
                with open(args.program) as f:
                    program = Program.from_json(f.read())
            except (OSError, ValueError, KeyError) as e:
                print(f"{args.program}: cannot load program: {e}",
                      file=sys.stderr)
                return 2
        mesh = args.mesh
        if args.plan:
            from paddle_tpu.analysis.planner import apply_plan
            program = program.clone()
            try:
                axes = apply_plan(program, args.plan)
            except (OSError, ValueError, TypeError) as e:
                print(f"{args.plan}: cannot apply plan: {e}",
                      file=sys.stderr)
                return 2
            if mesh is None:
                mesh = axes
        elif args.transpile:
            from types import SimpleNamespace
            from paddle_tpu.parallel.mesh import SP
            from paddle_tpu.transpiler import TranspileStrategy, transpile
            program = program.clone()
            strat = TranspileStrategy(
                sp_mode="ring" if int(mesh.get(SP, 1)) > 1 else None)
            transpile(program, mesh=SimpleNamespace(shape=dict(mesh)),
                      strategy=strat)
        passes = args.passes.split(",") if args.passes else None
        result = verify_program(program, feeds=args.feed,
                                fetches=args.fetch, mesh=mesh,
                                passes=passes)
        print(result.report())
        if not result.ok:
            rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
