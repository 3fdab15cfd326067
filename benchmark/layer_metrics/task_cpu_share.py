"""Operators: of the time the task threads could have run, how much they
did — the sum of the ``task`` events' ``cpu_s`` (thread-CPU seconds, taken
by the program at both ends of a task) over the sum of (task wall minus the
union of that task's ``host-sync`` and ``exchange-wait`` spans), all tasks
of the traced window.  Near 100 %: a thread runs when it can; near 100 / n:
n threads take turns on one interpreter.  None on a program whose task
events carry no ``cpu_s``."""

from harness import program_spans as P
from harness import sharing
from harness.deploy import say


def begin(run):
    return P.begin(run)


def read(run, since):
    evs = sharing.events(run)
    if evs is None:
        return None
    times = sharing.task_times(evs)
    if times is None:
        say("task_cpu_share: the program's task events carry no cpu_s")
        return None
    share = sharing.cpu_share(times)
    if share is None:
        return None
    wall = sum(t["wall"] for t in times)
    waited = sum(t["waited"] for t in times)
    cpu = sum(t["cpu"] for t in times)
    # the least cpu_s above zero shows the thread clock's step on this host
    step = min((t["cpu"] for t in times if t["cpu"] > 0), default=0.0)
    say(f"task_cpu_share: {len(times)} tasks, wall {wall:.6f} s, of it in "
        f"named waits {waited:.6f} s, thread CPU {cpu:.6f} s (least above "
        f"zero {step:.6f}); runnable and not running "
        f"{wall - waited - cpu:.6f} s")
    return share
