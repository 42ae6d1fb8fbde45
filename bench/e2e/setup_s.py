"""From the start of the process to the first timed step: start-up,
weights from the seed, conversion to tables, compiles (from the cache
after a cell's first run) and one execution of every prefill width and of
the decode step, plus any prefix the traffic registers."""


def read(run):
    return run.setup_s
