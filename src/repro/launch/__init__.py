"""Launch drivers: one-shot SSSP runs (sssp_run), query serving
(sssp_serve), dynamic-graph churn replay (sssp_dynamic), and the process
set-up they share (runtime).  Modules are imported on demand — the
drivers set device counts before JAX starts, so nothing is re-exported
here.
"""
