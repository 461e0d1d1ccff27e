"""JAX/Pallas reproduction + extension of "High-Performance
Parallelization of Dijkstra's Algorithm Using MPI and CUDA".

Subpackages: ``core`` (SSSP engines + graph containers), ``kernels``
(Pallas relax kernels), ``serve`` (query-serving subsystem), ``dynamic``
(mutable-graph overlays + incremental repair), ``obs`` (spans, counters,
cost records), ``launch`` (drivers).
"""
