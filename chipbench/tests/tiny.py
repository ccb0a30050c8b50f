"""Tiny cells for the CPU tests: the published configuration files with
every width cut, the traffic files with their sizes cut."""
import copy
import os

from chipbench import harness

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

QWEN_TINY = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                 vocab=256, head_dim=16)


def cell(config: str, traffic: str, sizes: dict, **traffic_sizes):
    conf = copy.deepcopy(harness.load_json(
        os.path.join(HERE, "configs", config + ".json")))
    # weights drawn wider as the width is cut, so that activations keep
    # the scale they have at the published width
    width = conf["program"]["d_model"]
    conf["program"].update(sizes)
    conf["initializer_range"] *= (width / conf["program"]["d_model"]) ** 0.5
    tr = dict(harness.load_json(os.path.join(HERE, "traffic",
                                             traffic + ".json")))
    tr.update(traffic_sizes)
    ref = harness.load_module(os.path.join(HERE, "configs", config + ".py"))
    return harness.Cell("tiny-" + traffic, 1, config, conf, ref, traffic,
                        tr, [], [])


def serve_cell(**kw):
    sizes = dict(batch=4, prompt_len=16, gen_len=8, sample_requests=3)
    sizes.update(kw)
    return cell("qwen2-1.5b", "decode-reasoning", QWEN_TINY, **sizes)
