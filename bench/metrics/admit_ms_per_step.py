"""Mean per traced step of the program's host span `serve.admit`
(runtime/server.py): pool statistics, admitting queued requests and the
prefix-trie sweep. Read from the run's profiler trace."""
from benchlib import spans

UNIT = "ms"


def read(ctx):
    return spans.phase_reading(ctx, "admit")
