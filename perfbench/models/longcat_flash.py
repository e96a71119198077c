"""Architecture ``longcat_flash``: a configuration file -> the program's model
(``deepspeed_tpu.models.longcat_flash``: two latent attentions and two dense
feed-forwards a layer, the routed experts on a shortcut beside them, a softmax
router with a choice bias over real and identity experts), its sharding
rules, and the size dictionary the plain reference reads.

**The depth's key.**  The published ``config.json`` calls the depth
``num_layers``: the file carries it under that name (the lint holds it to
``published`` and ``reduced``) AND as ``num_hidden_layers: {job: depth}``,
which is the key the harness reads a depth by (``share_faults``,
``traced_config``, ``jobs/serve.py``'s ``sizes``).  Both count LAYERS, each
with two attentions; the two have to agree (``depth_of`` says so where they
do not).  ``cache_entries_per_layer`` (2) is what the latent kernel's
roofline reader counts a layer's calls by: ``build`` holds it to the
program's own ``kv_cache_entries``.

**One chip's share** (``perfbench/README.md``).  Where the file has a
``share`` block, its ``n_routed_experts`` is the number of real experts HELD,
the router keeps the published width (``published.n_routed_experts`` real
experts + ``zero_expert_num`` identity ones, which are never cut: they have
no weights to hold), and the first expert held is ``share.this_chip`` x held.
``vocab_size`` is the slice run.
"""

import jax
import jax.numpy as jnp

#: the keys the configuration file, LongcatFlashConfig and the reference
#: share (the published ``config.json``'s own names)
KEYS = ("vocab_size", "hidden_size", "ffn_hidden_size",
        "expert_ffn_hidden_size", "num_attention_heads", "q_lora_rank",
        "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "mla_scale_q_lora", "mla_scale_kv_lora", "zero_expert_num",
        "moe_topk", "routed_scaling_factor", "rms_norm_eps", "rope_theta")
#: what the program's config also carries or checks, and the reference has no
#: use for
PROGRAM_KEYS = ("max_position_embeddings", "attention_bias",
                "attention_method", "zero_expert_type")


def depth_of(config, job):
    d = config["num_hidden_layers"]
    depth = int(d[job]) if isinstance(d, dict) else int(d)
    if depth != int(config["num_layers"]):
        raise ValueError(f"num_hidden_layers says {depth} layers and "
                         f"num_layers {config['num_layers']}")
    return depth


def held_experts(config):
    """``(real experts the router spans, experts held, first expert held)``."""
    held = int(config["n_routed_experts"])
    share = config.get("share")
    if not share:
        return held, held, 0
    return (int(config["published"]["n_routed_experts"]), held,
            int(share.get("this_chip", 0)) * held)


def reference_sizes(config, job):
    """The sizes the plain reference needs, as a flat dictionary."""
    sizes = {k: config[k] for k in KEYS}
    real, held, first = held_experts(config)
    sizes.update(num_hidden_layers=depth_of(config, job),
                 n_routed_experts=real, experts_held=held, first_expert=first)
    return sizes


def build(config, job):
    """``(model, tp_rules)`` of the program for this configuration and job."""
    from deepspeed_tpu.models import longcat_flash
    real, held, first = held_experts(config)
    fields = {k: config[k] for k in KEYS + PROGRAM_KEYS}
    fields.update(num_layers=depth_of(config, job), n_routed_experts=real,
                  experts_held=held, first_expert=first)
    fields.update(config.get("program", {}).get(job, {}).get("model", {}))
    cfg = longcat_flash.LongcatFlashConfig(**fields)
    stated = int(config.get("cache_entries_per_layer", 1)) * cfg.num_layers
    if stated != cfg.kv_cache_entries:
        raise ValueError(
            f"cache_entries_per_layer says {stated} cache entries (what "
            "serve_latent_kernel_roofline_share_by_call counts calls by) and "
            f"the program's model {cfg.kv_cache_entries}")
    return longcat_flash.LongcatFlashModel(cfg), longcat_flash.tp_rules(cfg)


def param_shapes(model):
    return jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]
