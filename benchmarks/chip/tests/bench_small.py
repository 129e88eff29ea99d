"""Cells of BENCHMARK.json cut to a size a CPU test run can hold."""
import json

import bench


# configurations kept under benchmarks/chip/ whose cells are not yet in
# BENCHMARK.json: (configuration file, mix, a listed cell whose metrics
# they report)
PENDING = {"minicpm2b.agentic.offline": ("minicpm-2b", "agentic.offline",
                                         "qwen05b.agentic.offline")}


def small_cell(name: str, seconds_rate: float = None) -> "bench.Cell":
    """``name`` from BENCHMARK.json (or :data:`PENDING`) with the model
    cut to a few layers of width 128 and the mix to prompts of at most
    256 tokens."""
    if name in PENDING:
        conf, mix, like = PENDING[name]
        cell = bench.load_cell(like)
        cell = bench.Cell(name=name, chips=1, config=json.loads(
            (bench.HERE / "configs" / f"{conf}.json").read_text()),
            traffic=bench.traffic_mod.load(mix),
            end_to_end=cell.end_to_end, per_layer=cell.per_layer)
    else:
        cell = bench.load_cell(name)
    c = cell.config
    c.update(hidden_size=128, num_attention_heads=4, num_key_value_heads=4,
             num_hidden_layers=2, intermediate_size=256, vocab_size=512)
    if "dim_model_base" in c:
        c["dim_model_base"] = 16
    c["serve"].update(max_seq=512, de_slots=4)
    t = cell.traffic
    t["sizes"] = {k: [s for s in v if s <= 128] for k, v in
                  t["sizes"].items()}
    if t["arrival"]["process"] == "poisson":
        t["arrival"]["per_s"] = seconds_rate or 3.0
    else:
        t["arrival"]["sessions"] = 24
    if t["kind"] == "sessions":
        t["think_mean_s"] = 0.05
    if t["kind"] == "single":
        t.update(prompt_mean=96, prompt_max=128)
    return cell
