"""trainer: median host milliseconds a training step spends inside
``StaticFunction.__call__``, from the program's own record of every compiled
call (``paddle_tpu.jit.call_timeline()``, field ``call_s``).

The three ``to_static_*_ms`` readers share what is here: the program's
newest ``LAST`` records of calls that compiled nothing (a 51-s window has
about 245; the last 64 lie after the traced slice, so the profiler does not
colour them), and nothing under ``FEWEST`` of them or from a program that
keeps no such timeline (before PR 25)."""
from statistics import median

LAST, FEWEST = 64, 8


def timeline():
    """The program's record of its compiled calls, or None from a program
    that keeps none."""
    from paddle_tpu import jit
    read = getattr(jit, "call_timeline", None)
    return None if read is None else read()


def steady_calls():
    recs = [r for r in timeline() or () if not r["fresh"]][-LAST:]
    return recs if len(recs) >= FEWEST else None


def median_ms(seconds):
    """Median over the steady calls of ``seconds(record)``, in ms."""
    recs = steady_calls()
    return None if recs is None else 1e3 * median(seconds(r) for r in recs)


def read(obs):
    return median_ms(lambda r: r["call_s"])
