"""The system under test, built from a configs/<config>.json: the repo's
GPT through its own constructor, weights drawn on the device from the seed.
The file's sizes (SIZE_KEYS, at its top level) are the configuration as it
is run; a preset that disagrees with them is an error, so the file cannot
drift from the code."""
from __future__ import annotations

SIZE_KEYS = ("n_layer", "n_head", "head_dim", "d_model", "d_ff", "seq_len",
             "vocab_size")


def sizes(cfg_json):
    return {k: int(cfg_json[k]) for k in SIZE_KEYS}


def build(cfg_json, seed):
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForPretraining, GPTModel

    paddle.seed(int(seed))
    cfg = GPTConfig.preset(cfg_json["preset"], dtype=cfg_json["dtype"],
                           dropout=0.0, **cfg_json.get("overrides", {}))
    got = {k: int(getattr(cfg, k)) for k in SIZE_KEYS if k != "head_dim"}
    got["head_dim"] = cfg.d_model // cfg.n_head
    want = sizes(cfg_json)
    if got != want:
        raise SystemExit(f"model.py: preset {cfg_json['preset']!r} gives "
                         f"{got}, the configuration file says {want}")
    return cfg, GPTForPretraining(GPTModel(cfg))
