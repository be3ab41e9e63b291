"""Mean time of manager.query_grouped per flush, numpy results back on the
host, ms."""
from bench.layers import mean_span_ms


def read(ctx):
    return mean_span_ms(ctx, "bench.query_grouped")
