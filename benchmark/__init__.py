"""The on-chip benchmark of adaptdl_tpu (see PERF.md; entry: run.py)."""
