"""Architecture ``mistral``: a configuration file -> the program's model
(``deepspeed_tpu.models.llama``), its sharding rules, and the size dictionary
the plain reference reads."""

import jax
import jax.numpy as jnp

#: configuration-file key -> LlamaConfig field, for the keys they share
KEYS = ("vocab_size", "hidden_size", "intermediate_size",
        "num_attention_heads", "num_key_value_heads", "rms_norm_eps",
        "rope_theta", "max_position_embeddings", "tie_word_embeddings")


def depth_of(config, job):
    d = config["num_hidden_layers"]
    return int(d[job]) if isinstance(d, dict) else int(d)


def program_fields(config, job):
    fields = {k: config[k] for k in KEYS}
    fields["num_hidden_layers"] = depth_of(config, job)
    fields["sliding_window"] = int(config.get("sliding_window") or 0)
    fields.update(config.get("program", {}).get(job, {}).get("model", {}))
    return fields


def build(config, job):
    """``(model, tp_rules)`` of the program for this configuration and job."""
    from deepspeed_tpu.models import llama
    cfg = llama.LlamaConfig(**program_fields(config, job))
    return llama.LlamaModel(cfg), llama.tp_rules(cfg)


def reference_sizes(config, job):
    """The sizes the plain reference needs, as a flat dictionary."""
    sizes = {k: config[k] for k in KEYS}
    sizes["num_hidden_layers"] = depth_of(config, job)
    sizes["sliding_window"] = int(config.get("sliding_window") or 0)
    return sizes


def param_shapes(model):
    return jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]
