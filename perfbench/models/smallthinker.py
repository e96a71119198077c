"""Architecture ``smallthinker``: a configuration file -> the program's model
(``deepspeed_tpu.models.smallthinker``: a block whose router reads the layer's
input, ReGLU experts of which this chip holds some, window layers with rotary
beside full layers without positions), its sharding rules, and the size
dictionary the plain reference and ``flops.py`` read."""

import jax
import jax.numpy as jnp

#: configuration-file key -> SmallThinkerConfig field, for the keys they share
KEYS = ("vocab_size", "hidden_size", "num_attention_heads",
        "num_key_value_heads", "head_dim", "max_position_embeddings",
        "rms_norm_eps", "rope_theta", "sliding_window_size",
        "moe_num_active_primary_experts", "moe_ffn_hidden_size",
        "moe_primary_router_apply_softmax", "norm_topk_prob",
        "tie_word_embeddings")


def depth_of(config, job):
    d = config["num_hidden_layers"]
    return int(d[job]) if isinstance(d, dict) else int(d)


def share_of(config):
    """``(router width, experts held, first expert)``: the file's
    ``moe_num_primary_experts`` is what this chip HOLDS where it states a
    ``share`` (chip ``this_chip`` of those that divide each layer evenly),
    and the router's width with none."""
    held = config["moe_num_primary_experts"]
    share = config.get("share")
    if not share:
        return held, held, 0
    return (held * share["chips_sharing_a_layer"], held,
            held * share.get("this_chip", 0))


def program_fields(config, job):
    depth = depth_of(config, job)
    width, held, first = share_of(config)
    fields = {k: config[k] for k in KEYS}
    fields.update(
        num_hidden_layers=depth, moe_num_primary_experts=width,
        experts_held=held, first_expert=first,
        sliding_window_layout=tuple(config["sliding_window_layout"][:depth]),
        rope_layout=tuple(config["rope_layout"][:depth]))
    fields.update(config.get("program", {}).get(job, {}).get("model", {}))
    return fields


def build(config, job):
    """``(model, tp_rules)`` of the program for this configuration and job."""
    from deepspeed_tpu.models import smallthinker
    cfg = smallthinker.SmallThinkerConfig(**program_fields(config, job))
    return smallthinker.SmallThinkerModel(cfg), smallthinker.tp_rules(cfg)


def reference_sizes(config, job):
    """The sizes the plain reference needs, as a flat dictionary, under the
    names ``flops.py`` reads where it reads them: one expert's width is
    ``intermediate_size``, the router's width ``num_local_experts``, the
    experts a token ``num_experts_per_tok``, the window ``sliding_window``
    (``flops.py`` counts every layer with it and every token's experts as
    held here: its count is approximate for a share and for mixed layers)."""
    width, held, first = share_of(config)
    sizes = {k: config[k] for k in (
        "vocab_size", "hidden_size", "num_attention_heads",
        "num_key_value_heads", "head_dim", "rms_norm_eps", "rope_theta",
        "sliding_window_size", "norm_topk_prob")}
    sizes.update(
        num_hidden_layers=depth_of(config, job),
        intermediate_size=config["moe_ffn_hidden_size"],
        num_local_experts=width,
        num_experts_per_tok=config["moe_num_active_primary_experts"],
        sliding_window=config["sliding_window_size"],
        sliding_window_layout=tuple(config["sliding_window_layout"]),
        rope_layout=tuple(config["rope_layout"]),
        experts_held=held, first_expert=first,
        # what the config has no key for (the file's `assumed`)
        router_input="layer_input", expert_activation="relu")
    return sizes


def param_shapes(model):
    return jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]
