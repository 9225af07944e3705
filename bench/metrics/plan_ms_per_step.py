"""Mean per traced step of the program's host span `serve.plan`
(runtime/server.py): scheduling, preemption, copy-on-write, block growth,
building the step's inputs and uploading them. Read from the run's profiler
trace."""
from benchlib import spans

UNIT = "ms"


def read(ctx):
    return spans.phase_reading(ctx, "plan")
