"""Mean per traced step of the program's host span `serve.sample`
(runtime/server.py): sampling each lane's token, emissions, retires and
prefix registration. Read from the run's profiler trace."""
from benchlib import spans

UNIT = "ms"


def read(ctx):
    return spans.phase_reading(ctx, "sample")
