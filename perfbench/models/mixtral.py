"""Architecture ``mixtral``: a configuration file -> the program's model
(``deepspeed_tpu.models.mixtral``: Mistral's block with a top-k router over
stacked experts), its sharding rules, and the size dictionary the plain
reference reads."""

import os

from perfbench.loader import load_file

_mistral = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "mistral.py"))
param_shapes = _mistral.param_shapes

#: the keys a routed block adds to Mistral's (configuration file and
#: MixtralConfig name them alike)
ROUTED_KEYS = ("num_local_experts", "num_experts_per_tok")


def reference_sizes(config, job):
    """The sizes the plain reference needs, as a flat dictionary."""
    sizes = _mistral.reference_sizes(config, job)
    sizes.update({k: config[k] for k in ROUTED_KEYS})
    return sizes


def build(config, job):
    """``(model, tp_rules)`` of the program for this configuration and job."""
    from deepspeed_tpu.models import mixtral
    fields = reference_sizes(config, job)
    fields.update(config.get("program", {}).get(job, {}).get("model", {}))
    cfg = mixtral.MixtralConfig(**fields)
    return mixtral.MixtralModel(cfg), mixtral.tp_rules(cfg)
