"""Share of the traced window a chip spends in collective operations that no
other device operation overlaps. The trace's ``XLA Ops`` line holds what the
core itself runs, one operation at a time: an asynchronous collective's
transfer runs beside it and is not there, so the self time of the operations
named ``all-reduce*``, ``all-gather*`` and ``reduce-scatter*`` on that line
(a synchronous collective, the start that issues one, the done that waits for
its end) is the time the core did nothing else. Averaged over the chips, as
``trace_reduce.summarize`` does. None without a trace; 0 where the program
has no such operation, as on one chip."""

import re

COLLECTIVE_OP = re.compile(r"^(all-reduce|all-gather|reduce-scatter)")


def exposed_seconds(trace) -> float:
    return sum(s for name, s in trace["ops"].items() if COLLECTIVE_OP.search(name))


def read(view):
    t = view["trace"]
    if not t or not t["window_s"]:
        return None
    sec = exposed_seconds(t)
    names = sorted(n for n in t["ops"] if COLLECTIVE_OP.search(n))
    print(f"collectives exposed: {sec * 1e3:.1f} ms of {t['window_s'] * 1e3:.1f} ms a chip "
          f"in {names}", flush=True)
    return 100.0 * sec / t["window_s"]
