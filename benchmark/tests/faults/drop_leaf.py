"""Planted fault ``drop_leaf``: an answer altered where it is produced.
Every third query's result loses the last object of its deepest list before
the handler encodes it — the response is well-formed JSON, HTTP 200, and one
traversed edge short."""

import copy
import itertools


def _deepest(node, depth=0):
    """(depth, list) of the deepest list of objects below ``node``."""
    best = (depth, None)
    if isinstance(node, dict):
        for v in node.values():
            if isinstance(v, list) and v and isinstance(v[0], dict):
                best = max(best, (depth + 1, v), key=lambda t: t[0])
                for child in v:
                    best = max(best, _deepest(child, depth + 1), key=lambda t: t[0])
    return best


def install():
    from dgraph_tpu.serve import server

    turn = itertools.count()
    plain = server.DgraphServer.run_query

    def run_query(self, text, *a, **kw):
        out = plain(self, text, *a, **kw)
        if "mutation" not in text and next(turn) % 3 == 2:
            out = copy.deepcopy(out)   # the result cache shares what it handed out
            _, lst = _deepest(out)
            if lst:
                lst.pop()
        return out

    server.DgraphServer.run_query = run_query
