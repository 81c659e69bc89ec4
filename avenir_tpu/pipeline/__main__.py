"""CLI — the conf-declared pipeline DAG as a runnable verb.

::

    python -m avenir_tpu.pipeline plan <conf> [-Dkey=value ...]
    python -m avenir_tpu.pipeline plan explain <conf> [-Dkey=value ...]
    python -m avenir_tpu.pipeline run <conf> [-Dkey=value ...] [--resume]

``plan`` (and its ``plan explain`` alias) loads the DAG declared by the
``pipeline.*`` properties (``Pipeline.from_conf``), lowers it through the
PlanGraft planner, and prints the fused plan tree — per-node cost
estimates and which rewrites (fuse / share-gram / prune / encode-once /
pack) fired — without executing anything.  ``run`` executes the pipeline;
``plan.on=true`` (conf or ``-D``) routes it through the planned program.
``-D`` overrides and ``conf.path``-free property files follow the main
``python -m avenir_tpu`` CLI's conventions.
"""

from __future__ import annotations

import sys
from typing import Dict, List, Tuple

USAGE = (
    "usage: python -m avenir_tpu.pipeline plan [explain] <conf> "
    "[-Dkey=value ...]\n"
    "       python -m avenir_tpu.pipeline run <conf> [-Dkey=value ...] "
    "[--resume]")


def parse_args(argv: List[str]) -> Tuple[str, str, Dict[str, str], bool]:
    """(verb, conf path, -D overrides, resume) from the argument list."""
    if not argv or argv[0] not in ("plan", "run"):
        raise SystemExit(USAGE)
    verb = argv[0]
    rest = argv[1:]
    if verb == "plan" and rest and rest[0] == "explain":
        rest = rest[1:]        # ``plan explain`` — same rendering
    overrides: Dict[str, str] = {}
    positional: List[str] = []
    resume = False
    for arg in rest:
        if arg == "--resume":
            resume = True
        elif arg.startswith("-D"):
            body = arg[2:]
            if "=" not in body:
                raise SystemExit(f"bad -D option (need -Dkey=value): {arg!r}")
            k, v = body.split("=", 1)
            overrides[k.strip()] = v.strip()
        else:
            positional.append(arg)
    if len(positional) != 1:
        raise SystemExit(USAGE)
    return verb, positional[0], overrides, resume


def main(argv: List[str]) -> int:
    verb, conf_path, overrides, resume = parse_args(argv)
    from avenir_tpu.core.config import JobConfig

    conf = JobConfig.from_file(conf_path)
    for k, v in overrides.items():
        conf.set(k, v)
    from avenir_tpu.pipeline.driver import Pipeline
    from avenir_tpu.utils import compile_cache

    compile_cache.configure()
    pipeline = Pipeline.from_conf(conf)
    if verb == "plan":
        from avenir_tpu.pipeline import plan as plan_mod

        pl = plan_mod.plan_pipeline(pipeline, resume=resume)
        print(pl.explain())
        return 0
    counters = pipeline.run(resume=resume)
    for name in counters:
        print(f"stage {name}")
        for group, vals in sorted(counters[name].as_dict().items()):
            print(f"  {group}")
            for k, v in sorted(vals.items()):
                print(f"\t{k}={v}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
