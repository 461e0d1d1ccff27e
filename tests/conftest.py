# NOTE: XLA_FLAGS / device-count forcing is deliberately NOT set here —
# smoke tests run on the single real CPU device.  Multi-device behavior is
# covered by subprocess tests (test_integration.py) that set
# --xla_force_host_platform_device_count in the child environment.
import heapq

import numpy as np
import pytest

# The sharded engines and their tests call jax's sharding API directly
# (jax >= 0.9: jax.shard_map, lax.pcast, jax.set_mesh); meshes come from
# repro.core._axes.make_mesh, which gives them Auto axes.


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def finite_close(a, b, rtol=1e-5):
    """allclose treating +inf as a big sentinel (unreachable vertices)."""
    a = np.where(np.isfinite(a), a, 1e30)
    b = np.where(np.isfinite(b), b, 1e30)
    return np.allclose(a, b, rtol=rtol)


def _out_adjacency(g):
    """Outgoing adjacency lists from a Graph, CsrGraph, or dense ndarray."""
    if hasattr(g, "indptr"):                      # CsrGraph: rows = incoming
        out = [[] for _ in range(g.n)]
        indptr, src, w = g.indptr, g.indices, g.weights
        for v in range(g.n):
            for e in range(int(indptr[v]), int(indptr[v + 1])):
                out[int(src[e])].append((int(v), float(w[e])))
        return out
    adj = np.asarray(g.adj if hasattr(g, "adj") else g)
    n = adj.shape[0]
    out = []
    for u in range(n):
        js = np.nonzero(np.isfinite(adj[u]))[0]
        out.append([(int(j), float(adj[u, j])) for j in js if j != u])
    return out


def dijkstra_oracle(g, source):
    """Independent pure-python Dijkstra: binary heap over adjacency lists.

    Deliberately shares no code with any engine (serial.py's numpy oracle
    mirrors Alg. 1's O(n²) scan; this is the classic heap formulation), so
    an agreement between the two oracles and an engine is three independent
    derivations of the same answer.  Accepts Graph, CsrGraph, or ndarray.
    Returns float64 distances, +inf for unreachable vertices.
    """
    out = _out_adjacency(g)
    n = len(out)
    dist = np.full(n, np.inf)
    dist[source] = 0.0
    done = np.zeros(n, bool)
    heap = [(0.0, source)]
    while heap:
        d, u = heapq.heappop(heap)
        if done[u]:
            continue
        done[u] = True
        for v, w in out[u]:
            nd = d + w
            if nd < dist[v]:
                dist[v] = nd
                heapq.heappush(heap, (nd, v))
    return dist
