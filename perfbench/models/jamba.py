"""Architecture ``jamba``: a configuration file -> the program's model
(``deepspeed_tpu.models.jamba``: Mamba-1 layers beside a few multi-query
attention layers without positions, a dense SwiGLU in every layer, a tied
table), its sharding rules, and the size dictionary the plain reference
reads.  Dense: the reference has no routing functions and is compared as a
dense model."""

import jax
import jax.numpy as jnp

#: the keys the configuration file, JambaConfig and the reference share (the
#: published ``config.json``'s own names)
KEYS = ("vocab_size", "hidden_size", "intermediate_size",
        "num_attention_heads", "num_key_value_heads", "attn_layer_period",
        "attn_layer_offset", "mamba_d_state", "mamba_d_conv", "mamba_expand",
        "mamba_dt_rank", "rms_norm_eps")
#: what the program's config also carries or checks, and the reference has no
#: use for
PROGRAM_KEYS = ("expert_layer_period", "expert_layer_offset", "num_experts",
                "num_experts_per_tok", "mamba_conv_bias", "mamba_proj_bias",
                "max_position_embeddings", "hidden_act",
                "tie_word_embeddings")


def depth_of(config, job):
    d = config["num_hidden_layers"]
    return int(d[job]) if isinstance(d, dict) else int(d)


def reference_sizes(config, job):
    """The sizes the plain reference needs, as a flat dictionary."""
    sizes = {k: config[k] for k in KEYS}
    sizes["num_hidden_layers"] = depth_of(config, job)
    return sizes


def build(config, job):
    """``(model, tp_rules)`` of the program for this configuration and job."""
    from deepspeed_tpu.models import jamba
    fields = {k: config[k] for k in KEYS + PROGRAM_KEYS}
    fields["num_hidden_layers"] = depth_of(config, job)
    fields["sliding_window"] = int(config.get("sliding_window") or 0)
    fields.update(config.get("program", {}).get(job, {}).get("model", {}))
    cfg = jamba.JambaConfig(**fields)
    return jamba.JambaModel(cfg), jamba.tp_rules(cfg)


def param_shapes(model):
    return jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]
