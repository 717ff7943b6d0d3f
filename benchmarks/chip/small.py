"""Reduced sizes of the benchmark's cells, for the tests on the CPU: the
same code paths at shapes a test run can hold."""
import harness

CONFIGS = {
    "resnet50-cifar32": {"stages": [[1, 64], [1, 128]],
                         "stem": {"kernel": 3, "stride": 1, "channels": 16},
                         "image_hw": 8, "reference_block_rows": 2},
    "stablelm2-1.6b-share": {"hidden_size": 64, "intermediate_size": 128,
                             "num_attention_heads": 4,
                             "num_key_value_heads": 4,
                             "num_hidden_layers": 2, "vocab_size": 256,
                             "reference_block_rows": 2},
}
TRAFFIC = {"per_worker_batch": 4, "interval": 2, "n_data": 256,
           "seq_len": 16}


def cell(name: str, impl: str = "auto") -> harness.Cell:
    """The workload ``name`` at reduced sizes, with its own limits."""
    full = harness.Cell(name)
    w = {x["name"]: x for x in full.manifest["workloads"]}[name]
    cfg = dict(full.cfg, **CONFIGS[w["config"]])
    cfg["coda"] = dict(cfg["coda"], impl=impl)
    traffic = dict(full.traffic, **TRAFFIC)
    if full.traffic["workers_per_chip"] > 1:
        traffic["workers_per_chip"] = 2
    return harness.Cell(name, cfg=cfg, traffic=traffic)


def v5e_peaks(kind: str) -> dict:
    """The v5e row of the peak table, for runs on a host without one."""
    return harness.load_json(harness.HERE / "peaks.json")["devices"][
        "TPU v5 lite"]
