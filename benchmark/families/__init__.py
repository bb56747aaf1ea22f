"""One file a model family: everything the drivers and the roofline readers
need to know about a model, found by the name in the configuration file.

    fam = families.of(run["cfg"])        # families/<cfg["family"]>.py

A configuration file without a "family" key is of the family `gpt`. A
family module gives (families/gpt.py is the pattern):

  build(cfg_json, seed) -> (cfg, model)   the system under test, through the
                          program's own constructor, its sizes checked
                          against the file; drivers read cfg.seq_len
  sizes(cfg_json)         the file's sizes, under the family's own keys
  vocab_size(cfg_json)    the ids the traffic may draw: 0 .. vocab_size - 1
  criterion()             the training loss, as the program's users build it
  reference_scorer(cfg_json, cfg, model, padded_len, positions)
                          -> score(ids[L], at[K]) -> logits[K, V]: the plain
                          reference (reference/<file>.py), compiled and
                          warmed, over the served model's own weights

and the least work the cell's shapes demand, for the shares of a roofline.
Each takes `run` whole (the family picks the counters and sizes it needs)
and returns None where the family has nothing to say, so that the reader
leaves its metric out of the line:

  train_flops_per_token(run)        model FLOPs a trained token
  decode_step_work(run)             (flops, bytes) of a mean decode step
  kernel_work(run, kernel)          (flops, bytes) of one layer's call of
                                    "flash_fwd", "flash_bwd" or
                                    "paged_attention" on one chip
"""
from __future__ import annotations

import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def of(cfg_json):
    """The family module of a configuration, loaded once a process."""
    name = cfg_json.get("family", "gpt")
    key = "bench_family_" + name.replace("-", "_").replace(".", "_")
    if key not in sys.modules:
        path = os.path.join(HERE, name + ".py")
        if not os.path.exists(path):
            raise SystemExit(
                f"families: configuration {cfg_json.get('name')!r} is of "
                f"family {name!r}, and there is no families/{name}.py")
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[key] = mod
    return sys.modules[key]
