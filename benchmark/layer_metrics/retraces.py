"""compiled step: executables built after warm-up, ``_traces_total()`` after
the run minus before the schedule started. Any value but 0 also makes the
run not ``correct``."""


def read(obs):
    return obs.get("retraces")
