"""Seconds in the packer's Python loop over the entities
(``group_entities_into_buckets``), all calls of the process: the total of the
program's ``timing/pack/group_entities`` histogram (set-up)."""
from benchmark import program_trace


def read(ctx):
    return program_trace.total("timing/pack/group_entities")
