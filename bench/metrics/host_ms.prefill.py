"""Host time a chunk-lane round spends in ``Scheduler.step`` outside its
one blocking read (the program's ``serve.admin``, ``serve.assemble``,
``serve.dispatch`` and ``serve.deliver`` spans), ms, mean over the traced
rounds."""
from bench.readers import host_ms


def read(ctx):
    return host_ms(ctx, chunk_lane=True)
