"""A cell of BENCHMARK.json at ``reduced()`` widths and short lengths, for
driving the harness on the CPU (the fused backend's kernels then run in
interpret mode)."""
import copy

import spec
import system

#: wider than ``reduced()`` (d_model 64), so that int8 rounding moves the
#: outputs about as little, relative to the int4 control, as it does at the
#: published widths; two layers keep interpret mode quick
WIDTHS = dict(num_layers=2, d_model=256, num_heads=4, head_dim=64, d_ff=1024,
              vocab_size=512)

SMALL = {"encoder": {"tokens": [8, 16], "rate": 8.0, "max_batch": 2},
         "decode": {"prompt": [4, 12], "output": [3, 6], "rate": 3.0,
                    "slots": 2}}


def reduced_cell(name: str, monkeypatch):
    from repro.configs import get_config
    cell = spec.cell(name)
    cell.config = copy.deepcopy(cell.config)
    cell.traffic = copy.deepcopy(cell.traffic)
    arch = get_config(cell.config["registry"]).reduced().replace(**WIDTHS)
    monkeypatch.setattr(system, "arch_config", lambda config: arch)
    cell.config.update(
        num_hidden_layers=arch.num_layers, hidden_size=arch.d_model,
        num_attention_heads=arch.num_heads,
        num_key_value_heads=arch.num_kv_heads,
        intermediate_size=arch.d_ff, vocab_size=arch.vocab_size)
    small = SMALL[cell.kind]
    cell.traffic["arrivals"]["rate"] = small["rate"]
    for c in cell.traffic["mix"]:
        for key in ("tokens", "prompt", "output"):
            if key in c:
                c[key] = list(small[key])
    if cell.kind == "encoder":
        cell.config["engine"]["max_batch"] = small["max_batch"]
    else:
        cell.config["engine"]["slots"] = small["slots"]
    return cell
