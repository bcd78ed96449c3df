"""Mean device time of one engine launch (``jit_decode_fn`` or
``jit_prefill_fn`` module event) in the traced stretch."""


def read(run):
    launches = run.trace.get("launches") if run.trace else None
    if not launches:
        return None
    return sum(d for _, d in launches) / len(launches) / 1e6
