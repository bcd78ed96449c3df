"""Host time per engine step: the program's ``serve.step`` span less its
``serve.phase.*`` child (the launch), averaged over the traced steps.
What is left is admission, planning, the guard's drain, the host
``isfinite`` scan, sampling and routing."""


def read(run):
    steps = [s for s in run.spans if s[0] == "serve.step"]
    phases = [s for s in run.spans if s[0].startswith("serve.phase.")]
    if not steps:
        return None
    own = []
    for _, t0, t1 in steps:
        child = sum(e - s for _, s, e in phases if s >= t0 and e <= t1)
        own.append((t1 - t0) - child)
    return 1e3 * sum(own) / len(own)
