"""Host seconds packing rows into entity buckets and assembling the data sets (set-up)."""


def read(ctx):
    spans = ctx["spans"].durations("pack")
    return sum(spans) if spans else None
