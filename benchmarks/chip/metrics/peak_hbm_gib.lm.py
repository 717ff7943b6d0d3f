"""``peak_hbm_gib`` in the cells that report ``tokens_per_s``: the same
reader, under a name of its own with a bound of its own (PERF.md)."""
import harness

read = harness.load_module(harness.HERE / "metrics" / "peak_hbm_gib.py").read
