"""Train gang: seconds from the `fit()` call to the loop's first statement
in the worker (placement group, worker actors, backend set-up). Host clock,
two processes on one machine."""


def read(record):
    return record.get("clock", {}).get("gang_start_s")
