"""Architecture ``evabyte``: a configuration file -> the program's model
(``deepspeed_tpu.models.evabyte``: exact attention inside a window, chunk
summaries beyond it, eight prediction heads), its sharding rules, and the
size dictionary the plain reference reads."""

import os

from perfbench.loader import load_file

_mistral = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "mistral.py"))
depth_of = _mistral.depth_of
param_shapes = _mistral.param_shapes

#: the keys the configuration file, EvaByteConfig and the reference share
#: (the published ``config.json``'s own names)
KEYS = ("vocab_size", "hidden_size", "intermediate_size",
        "num_attention_heads", "num_key_value_heads", "rms_norm_eps",
        "rope_theta", "max_position_embeddings", "tie_word_embeddings",
        "window_size", "chunk_size", "num_pred_heads",
        "norm_add_unit_offset")
#: what the program's config also checks, and the reference has no use for
PROGRAM_KEYS = ("attention_class", "hidden_act", "attention_bias")


def reference_sizes(config, job):
    """The sizes the plain reference needs, as a flat dictionary."""
    sizes = {k: config[k] for k in KEYS}
    sizes["num_hidden_layers"] = depth_of(config, job)
    return sizes


def build(config, job):
    """``(model, tp_rules)`` of the program for this configuration and job."""
    from deepspeed_tpu.models import evabyte
    fields = reference_sizes(config, job)
    fields.update({k: config[k] for k in PROGRAM_KEYS})
    fields.update(config.get("program", {}).get(job, {}).get("model", {}))
    cfg = evabyte.EvaByteConfig(**fields)
    return evabyte.EvaByteModel(cfg), evabyte.tp_rules(cfg)
