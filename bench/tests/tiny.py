"""Cells of the benchmark cut to a size a CPU test can run: the same
configuration files, traffic mixes, engine and harness, with widths,
lengths and windows made small. The limits of ``correct`` stay those of
the configuration."""
import copy
import json

from bench import run

#: cells the tests run that BENCHMARK.json does not hold: the open-loop
#: chat mix with nothing shared (PERF.md, Open questions), reporting the
#: metrics of the open-loop cell named ``like``
EXTRA = {"internlm2-chat": dict(config="internlm2-1.8b", traffic="chat",
                                chips=1, like="internlm2-prefix")}

#: small widths per configuration (published keys)
SMALL = {
    "internlm2-1.8b": dict(hidden_size=128, intermediate_size=256,
                           num_hidden_layers=4, num_attention_heads=4,
                           num_key_value_heads=2, vocab_size=512),
    "deepseek-moe-16b": dict(hidden_size=64, intermediate_size=128,
                             moe_intermediate_size=32, num_hidden_layers=3,
                             num_attention_heads=4, num_key_value_heads=4,
                             n_routed_experts=8, num_experts_per_tok=2,
                             vocab_size=256),
}


def _bench(name: str) -> dict:
    bench = json.loads(run.BENCHMARK.read_text())
    if name in EXTRA:
        extra = dict(EXTRA[name])
        like = extra.pop("like")
        bench["workloads"].append(dict(name=name, **extra))
        for m in bench["end_to_end"] + bench["per_layer"]:
            if like in m.get("workloads", ()):
                m["workloads"].append(name)
    return bench


def tiny_cell(name: str) -> run.Cell:
    cell = run.load_cell(name, _bench(name))
    conf = copy.deepcopy(cell.config)
    conf.update(SMALL[conf["name"]])
    conf["program"]["set"]["head_dim"] = (conf["hidden_size"]
                                          // conf["num_attention_heads"])
    conf["engine"] = dict(n_dp_groups=2, max_batch=4, max_len=512)
    if "n_routed_experts" in conf:
        conf["program"]["set"]["moe.capacity_factor"] = 4.0
    mix = copy.deepcopy(cell.mix)
    mix["warm_seconds"] = 1.0
    if mix["loop"] == "closed":
        mix["clients"] = 10
    if mix["prompt"].get("max", 0) > 448:
        # prompts that fit the small buffer beside their output
        mix["prompt"] = dict(mix["prompt"], max=448,
                             median=min(mix["prompt"]["median"], 256))
    if mix["output"].get("max", 0) > 64:
        # a busy CPU decodes a few tens of tokens a second per slot:
        # requests short enough to finish inside a test's window
        mix["output"] = dict(mix["output"], min=16, max=64, median=32)
    if "system_prompt" in mix:
        # a shared head of 128 tokens, sessions capped at 192: every
        # prompt lies in one 256-token buffer, as the cell's lie in 2,048
        mix["system_prompt"] = 128
        small = dict(dist="lognormal", median=24, sigma=0.4, min=8, max=64)
        mix["prompt"] = dict(small)
        mix["sessions"]["extend"] = dict(small)
        mix["sessions"]["max_prompt"] = 192
        mix["output"] = dict(dist="uniform", min=4, max=16)
        conf["engine"].update(max_len=256)
    cell.config, cell.mix = conf, mix
    return cell
