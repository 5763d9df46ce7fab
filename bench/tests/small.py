"""The cells at a size the CPU runs in seconds: the same drivers, the
same traffic generator and the same reference, smaller numbers."""

import jax

import run

BENCH = run.load_json(run.ROOT / "BENCHMARK.json")


# configurations kept on file for later cells (PERF.md, Open
# questions), run here under the traffic and metrics they were built for
ON_FILE = {
    "tiara-paper-mix-4node": ("qd320", 4, ["posts_per_s", "post_p99_ms"]),
    "internlm2-1.8b-tiara": ("decode32", 1,
                             ["decode_tokens_per_s", "tpot_p99_ms"]),
}


def spec(name: str) -> dict:
    """The spec of the cell ``name`` of BENCHMARK.json, or of a
    configuration kept on file."""
    if name not in ON_FILE:
        s = run.cell_spec(BENCH, name)
    else:
        traffic, chips, metrics = ON_FILE[name]
        s = {"cell": {"name": name, "chips": chips},
             "config": run.load_json(run.BENCH / "configs" / f"{name}.json"),
             "traffic": run.load_json(run.BENCH / "traffic"
                                      / f"{traffic}.json"),
             "end_to_end": [{"name": m, "unit": "-"}
                            for m in metrics + ["setup_s"]],
             "per_layer": [], "bench_dir": run.BENCH}
    cfg, traffic = s["config"], s["traffic"]
    if cfg["driver"] == "fabric":
        cfg["graph"]["n_nodes"] = 1024
        cfg["reply_slots"] = 16
        traffic.update(outstanding_per_tenant=4, ring_size=10,
                       max_posts_per_tenant=4000, warmup_waves=2)
    else:
        cfg["model"].update(hidden_size=256, intermediate_size=512,
                            num_attention_heads=4, num_key_value_heads=2,
                            num_hidden_layers=2, vocab_size=8000)
        cfg["serving"].update(max_seq=128, page_tokens=32)
        traffic.update(slots=4, prompt_tokens=[8, 24],
                       output_tokens=[30, 90], max_requests=2000,
                       check_sequences=16)
        # this size's own limit, set as the full size's is: sound runs
        # read 0.011-0.031 and the float8 control 0.181-0.272 (seeds 11,
        # 12, 13 at vocabularies of 8000 and 32000, on the CPU)
        cfg["check"]["served_logit_gap_limit"] = 0.12
    return s


def cpu(chips: int) -> list:
    return jax.devices()[:chips]


def run_small(workload: str, seed: int = 2**31 + 7, seconds: float = 1.0,
              **kw) -> dict:
    return run.run_cell(spec(workload), seed, seconds, False,
                        find_devices=cpu, cache=False, **kw)
