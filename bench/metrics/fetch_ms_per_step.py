"""Mean per traced step of the program's host span `serve.fetch`
(runtime/server.py): the device-to-host copy of the step's logits. Read
from the run's profiler trace."""
from benchlib import spans

UNIT = "ms"


def read(ctx):
    return spans.phase_reading(ctx, "fetch")
