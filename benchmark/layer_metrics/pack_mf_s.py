"""Seconds packing the matrix-factorization coordinate's two bucket sets
(``build_mf_dataset``), all calls of the process: the total of the program's
``timing/pack/mf_side_buckets`` histogram (set-up). A program that does not
time its packer (a parent commit) reads nothing."""
from benchmark import program_trace


def read(ctx):
    return program_trace.total("timing/pack/mf_side_buckets")
