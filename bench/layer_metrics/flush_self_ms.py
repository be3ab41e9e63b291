"""Mean time of CubeGraphService.flush outside manager.query_grouped
(grouping, splitting, materialize), ms per flush."""
from bench.layers import flush_self_ms


def read(ctx):
    return flush_self_ms(ctx)
