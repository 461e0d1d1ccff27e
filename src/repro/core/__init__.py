# The paper's SSSP engines (serial, dense and CSR fixpoints, frontier,
# Δ-stepping, the sharded families) and the graph containers they read;
# core/api.py is the one facade over them.
