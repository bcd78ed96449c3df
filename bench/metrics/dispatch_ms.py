"""Host time per traced step spent enqueueing the engine's launch: the
program's ``serve.launch.dispatch`` spans (the jitted call, up to its
return), summed over the traced steps and divided by their number. The
wait for the launch's logits is ``serve.launch.wait``, not counted here.
A program without the span gives nothing."""


def read(run):
    spans = [e - s for name, s, e in run.spans
             if name == "serve.launch.dispatch"]
    if not spans or not run.steps:
        return None
    return 1e3 * sum(spans) / len(run.steps)
