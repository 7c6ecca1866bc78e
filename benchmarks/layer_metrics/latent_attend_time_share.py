"""Device time of the operations named ``latent_attend_*`` (the latent
family's kernels that attend over the pages a sequence holds: the decode
step's and the prefill chunk's) over device busy time. None where the trace
names no such kernel: a program that gathers the table and scores it in
fusions nobody can tell apart."""


def read(view):
    t = view["trace"]
    if not t:
        return None
    sec = sum(s for name, s in t["ops"].items() if name.startswith("latent_attend_"))
    return 100.0 * sec / t["busy_s"] if sec and t["busy_s"] else None
