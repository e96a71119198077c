"""Architecture ``motif``: a configuration file -> the program's model
(``deepspeed_tpu.models.motif``: grouped differential attention over a latent
cache with a window on three layers of four, a four-stream mHC residual,
PolyNorm-gated feed-forwards, leading dense layers and then sigmoid-routed
experts beside one shared expert), its sharding rules, and the size
dictionary the plain reference reads.

**One chip's share** (``perfbench/README.md``).  Where the file has a
``share`` block, its ``num_experts`` is the number of experts HELD, the
router keeps the published width (``published.num_experts``), and the first
expert held is ``share.this_chip`` x held: the program's model gets
``num_experts`` (the router's width), ``experts_held`` and ``first_expert``,
the reference ``experts_held`` and ``first_expert`` (it takes the router's
width from the gate's own shape).  ``vocab_size`` is the slice run.  The
depth counts the leading dense layers (``n_dense_first_layers``, never cut)
and the routed layers after them.

The published ``config.json`` also carries keys that select nothing here
(``perfbench/configs/motif3_beta_1chip.json``, ``assumed``): ``build`` holds
those that name a mechanism to what is implemented, by name.
"""

import jax
import jax.numpy as jnp

#: the keys the configuration file, MotifConfig and the reference share (the
#: published ``config.json``'s own names)
KEYS = ("vocab_size", "hidden_size", "intermediate_size",
        "moe_intermediate_size", "n_dense_first_layers",
        "num_attention_heads", "num_key_value_heads", "num_noise_heads",
        "head_dim", "qk_rope_head_dim", "v_head_dim", "q_lora_rank",
        "kv_lora_rank", "experts_top_k", "route_norm", "route_scale",
        "sliding_window", "sliding_window_period", "mhc_expansion_rate",
        "mhc_sinkhorn_iters", "polynorm_output_scale", "polynorm_bias_clamp",
        "rms_norm_eps", "rope_theta", "hidden_act")
#: what the program's config also carries or checks, and the reference has no
#: use for
PROGRAM_KEYS = ("num_shared_experts", "score_func", "sliding_window_pattern",
                "swa_rope_theta", "max_position_embeddings", "attention_cls",
                "diff_v2", "elementwise_attn_output_gate",
                "tie_word_embeddings")
#: published keys that select nothing the program implements otherwise
HELD_TO = {"mhc_enabled": True, "use_sliding_window": True,
           "headwise_attn_output_gate": False, "score_before_experts": False,
           "interleave_moe_layer_step": 1, "mscale": 1,
           "polynorm_output_scale_per_layer": {}}


def depth_of(config, job):
    d = config["num_hidden_layers"]
    return int(d[job]) if isinstance(d, dict) else int(d)


def held_experts(config):
    """``(router width, experts held, first expert held)``."""
    held = int(config["num_experts"])
    share = config.get("share")
    if not share:
        return held, held, 0
    return (int(config["published"]["num_experts"]), held,
            int(share.get("this_chip", 0)) * held)


def reference_sizes(config, job):
    """The sizes the plain reference needs, as a flat dictionary."""
    sizes = {k: config[k] for k in KEYS}
    _, held, first = held_experts(config)
    sizes.update(num_hidden_layers=depth_of(config, job), experts_held=held,
                 first_expert=first)
    return sizes


def build(config, job):
    """``(model, tp_rules)`` of the program for this configuration and job."""
    from deepspeed_tpu.models import motif
    for key, value in HELD_TO.items():
        if config.get(key, value) != value:
            raise NotImplementedError(
                f"{key}: {config[key]!r}; the program's Motif model has "
                f"{value!r} alone")
    if (config.get("rope_scaling") or {}).get("apply_yarn_scaling"):
        raise NotImplementedError("yarn scaling of the rotary")
    width, held, first = held_experts(config)
    fields = {k: config[k] for k in KEYS + PROGRAM_KEYS}
    fields.update(num_hidden_layers=depth_of(config, job), num_experts=width,
                  experts_held=held, first_expert=first,
                  rope_theta=float(config["rope_theta"]),
                  swa_rope_theta=float(config["swa_rope_theta"]),
                  route_scale=float(config["route_scale"]))
    fields.update(config.get("program", {}).get(job, {}).get("model", {}))
    cfg = motif.MotifConfig(**fields)
    return motif.MotifModel(cfg), motif.tp_rules(cfg)


def param_shapes(model):
    return jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]
