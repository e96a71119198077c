"""Architecture ``ouro``: a configuration file -> the program's model
(``deepspeed_tpu.models.ouro``: ONE stack of sandwich-norm layers run
``total_ut_steps`` times a token, the final norm and an exit gate after every
pass), its sharding rules, and the size dictionary the plain reference reads.

The published ``config.json`` also says what this model does NOT do, and the
program has no switch for (``hidden_act`` other than silu, a rotary scaling,
a sliding window, a layer type other than full attention): ``build`` holds
those keys to what is implemented, by name, and does not pass them on.
"""

import dataclasses

import jax
import jax.numpy as jnp

#: the keys the configuration file, OuroConfig and the reference share (the
#: published ``config.json``'s own names)
KEYS = ("vocab_size", "hidden_size", "intermediate_size",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "rms_norm_eps", "rope_theta", "total_ut_steps")
#: what the program's config also carries and the reference has no use for
PROGRAM_KEYS = ("max_position_embeddings", "tie_word_embeddings",
                "early_exit_threshold")
#: published keys that select nothing the program implements otherwise
HELD_TO = {"hidden_act": "silu", "rope_scaling": None,
           "sliding_window": None, "use_sliding_window": False}


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
    from deepspeed_tpu.models import ouro
    for key, value in HELD_TO.items():
        if config.get(key, value) != value:
            raise NotImplementedError(
                f"{key}: {config[key]!r}; the program's Ouro model has "
                f"{value!r} alone")
    if set(config.get("layer_types") or ["full_attention"]) != {
            "full_attention"}:
        raise NotImplementedError("layer_types other than full_attention")
    fields = {k: config[k] for k in KEYS + PROGRAM_KEYS}
    fields.update(num_hidden_layers=depth_of(config, job),
                  rope_theta=float(config["rope_theta"]),
                  early_exit_threshold=float(config["early_exit_threshold"]))
    fields.update(config.get("program", {}).get(job, {}).get("model", {}))
    cfg = ouro.OuroConfig(**fields)
    return ouro.OuroModel(cfg), ouro.tp_rules(cfg)


def param_shapes(model):
    """The parameter tree's shapes.  They depend neither on the number of
    passes (the weights are shared) nor on the longest position, so the
    shapes are taken from ONE pass over a short table of angles: a quarter of
    the tracing, and no table of 65 536 rows a layer."""
    once = type(model)(dataclasses.replace(
        model.config, total_ut_steps=1, max_position_embeddings=8))
    return jax.eval_shape(once.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]
