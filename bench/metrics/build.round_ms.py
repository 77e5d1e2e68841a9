"""build.round_ms: device busy time of the traced builds over the rounds
they ran, t1·(t2+1)-1 per build (update rounds and reverse-edge rounds;
the random init is counted in with them), in ms.  The traced window
holds whole builds only (bench/drive.py `build`)."""


def read(run):
    if run.trace is None:
        return None
    rounds = len(run.trace.host_spans("build")) * run.counters["rounds"]
    return 1e3 * run.trace.busy_s() / rounds if rounds else None
