"""Host milliseconds per round spent outside the engine's round-block call
and its wait: block-edge evaluation, accounting and logging."""


def read(run):
    if run.rounds <= 0:
        return None
    return (run.hi - run.lo - run.run_ns) / 1e6 / run.rounds
