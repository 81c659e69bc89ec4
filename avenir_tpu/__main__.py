"""CLI — the ``hadoop jar avenir-1.0.jar <ToolClass> -Dconf.path=<props>
<in> <out>`` contract as ``python -m avenir_tpu <JobName> -Dconf.path=<props>
<in> <out>``.

Accepts the reference's fully-qualified class names or simple names, ``-D``
property overrides (applied over the properties file, as Hadoop's
GenericOptionsParser does), and prints the job counters on completion the way
the Hadoop job client did.
"""

from __future__ import annotations

import sys
from typing import Dict, List, Tuple


def parse_args(argv: List[str]) -> Tuple[str, Dict[str, str], List[str]]:
    if not argv:
        raise SystemExit(
            "usage: python -m avenir_tpu <JobName> [-Dkey=value ...] <input> <output>\n"
            "       python -m avenir_tpu --list")
    job_name = argv[0]
    overrides: Dict[str, str] = {}
    positional: List[str] = []
    for arg in argv[1:]:
        if arg == "--resume":
            # sugar for -Dstream.resume=true (restore the latest
            # stream.checkpoint.dir snapshot and continue from its cursor)
            overrides["stream.resume"] = "true"
            continue
        if arg.startswith("-D"):
            body = arg[2:]
            if "=" not in body:
                raise SystemExit(f"bad -D option (need -Dkey=value): {arg!r}")
            k, v = body.split("=", 1)
            overrides[k.strip()] = v.strip()
        else:
            positional.append(arg)
    return job_name, overrides, positional


def main(argv: List[str]) -> int:
    import os
    # CrossGraft: a worker spawned by the fleet launcher (python -m
    # avenir_tpu.launch) carries its rank in the environment — join the
    # fleet BEFORE any jax work, through the hardened bounded coordinator
    # join (a bad coordinator raises a typed LaunchError, never hangs)
    if os.environ.get("AVENIR_NUM_PROCESSES"):
        from avenir_tpu.launch import join_from_env
        join_from_env()
    from avenir_tpu.core.config import JobConfig
    from avenir_tpu.jobs import REGISTRY, get_job

    if argv and argv[0] in ("--list", "list"):
        for name in sorted(k for k in REGISTRY if "." not in k):
            print(name)
        return 0
    job_name, overrides, positional = parse_args(argv)
    conf_path = overrides.pop("conf.path", None)
    conf = JobConfig.from_file(conf_path) if conf_path else JobConfig()
    for k, v in overrides.items():
        conf.set(k, v)
    # launcher-assigned journal shard suffix: adopted unless the conf
    # (file or -D) names its own — the per-process trace.writer.suffix
    # contract the fleet launcher's teardown merge relies on
    if os.environ.get("AVENIR_WRITER_SUFFIX") and \
            not conf.get("trace.writer.suffix"):
        conf.set("trace.writer.suffix", os.environ["AVENIR_WRITER_SUFFIX"])
    if len(positional) != 2:
        raise SystemExit(f"expected <input> <output>, got {positional}")
    job = get_job(job_name)
    # persistent compilation cache: a one-shot CLI job's wall time is
    # dominated by first compiles (~tens of seconds on TPU) — repeat
    # invocations of the same job shapes skip them.  Placed here so --list
    # and usage errors touch nothing.
    from avenir_tpu.utils import compile_cache
    compile_cache.configure()
    counters = job.run(conf, positional[0], positional[1])
    # (the final counter snapshot is journaled by Job.run itself under
    # the job's name — round 15 moved it there so multi-process workers
    # and Python-API callers snapshot too, not just this CLI)
    for group, vals in sorted(counters.as_dict().items()):
        print(group)
        for k, v in sorted(vals.items()):
            print(f"\t{k}={v}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))


def cli() -> None:
    """console-script entry point (pyproject.toml [project.scripts])."""
    raise SystemExit(main(sys.argv[1:]))
