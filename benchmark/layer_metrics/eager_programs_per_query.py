"""Compiled programs: program executions on the device (the ``XLA Modules``
line) that are not the engine's own named programs (``jit_trino_*``), per
traced query — eager ``jnp`` operations outside any jit, one tiny launch
each.  Prints device seconds, executions and mean per program, by stable
name, for the ten with most device time.  Nothing to read where the program
names none of its programs (before PR 26)."""

import re

from harness import trace as T
from harness.deploy import say

OWN = "jit_trino_"
TOP = 10


def stable(name: str) -> str:
    """``jit_trino_kernels_compact(1234)`` -> ``jit_trino_kernels_compact``."""
    return re.sub(r"\(.*$", "", name)


def read(run, _):
    runs = [(d, stable(name)) for p in run.trace.programs.values()
            for _, d, name in T.clip(p, *run.trace.window)]
    if not any(name.startswith(OWN) for _, name in runs):
        return None
    seconds, count = {}, {}
    for d, name in runs:
        seconds[name] = seconds.get(name, 0.0) + d
        count[name] = count.get(name, 0) + 1
    eager = sum(n for name, n in count.items() if not name.startswith(OWN))
    say(f"eager_programs_per_query: {len(runs)} program executions in the "
        f"window, {eager} not named {OWN}* ({len(count)} distinct names); "
        f"most device time by program: "
        + "; ".join(f"{name} {sec:.6f} s in {count[name]} "
                    f"(mean {sec / count[name] * 1e3:.3f} ms)"
                    for name, sec in sorted(
                        seconds.items(), key=lambda kv: -kv[1])[:TOP]))
    say("eager_programs_per_query: every name that is not the engine's, "
        "by executions: "
        + "; ".join(f"{name} x{n}" for name, n in sorted(
            ((k, v) for k, v in count.items() if not k.startswith(OWN)),
            key=lambda kv: (-kv[1], kv[0]))))
    return eager / run.queries
