"""``mfu`` in the cells that report ``tokens_per_s``: the same reader,
under a name of its own because it moves that metric."""
import harness

read = harness.load_module(harness.HERE / "metrics" / "mfu.py").read
