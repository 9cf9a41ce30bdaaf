"""A dense decoder configuration as the benchmark reads it, and the
weights it makes for one.

A configuration file holds the published keys under the source's own
names; its ``program_keys`` maps each size the program and the
reference need onto one of those keys.  ``dims`` resolves that map, so
both sides read the same numbers.

The frozen base and the clients' initial LoRA adapters are made here, on
the device, from the seed, in the layout the program takes them in:
``{"embed", "final_norm", ["lm_head"], "groups": ({"ln", "wq", "wkv",
"wo", "ln2", "w_in", "w_out"} stacked over layers,)}``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LORA_TARGETS = ("wq", "wkv", "wo", "w_in", "w_out")


def dims(config: dict) -> dict:
    """The sizes the run uses, under the program's names."""
    out = {k: config[v] for k, v in config["program_keys"].items()}
    out.setdefault("head_dim", out["d_model"] // out["n_heads"])
    out["rank"] = int(config["lora"]["rank"])
    out["alpha"] = float(config["lora"]["alpha"])
    return out


def program_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import LoRAConfig, ModelConfig
    d = dims(config)
    return ModelConfig(
        name=config["name"], arch_type="dense", source=config["source"],
        n_layers=d["n_layers"], d_model=d["d_model"], n_heads=d["n_heads"],
        n_kv_heads=d["n_kv_heads"], head_dim=d["head_dim"], d_ff=d["d_ff"],
        vocab_size=d["vocab_size"], pattern=(("attn", "mlp"),),
        rope_theta=float(d["rope_theta"]), norm_eps=float(d["norm_eps"]),
        tie_embeddings=bool(d["tie_embeddings"]), dtype="float32",
        lora=LoRAConfig(rank=d["rank"], alpha=d["alpha"],
                        targets=LORA_TARGETS))


def shapes(d: dict) -> dict:
    """(d_in, d_out) of each adapted projection of one layer."""
    hd = d["n_heads"] * d["head_dim"]
    return {"wq": (d["d_model"], hd),
            "wkv": (d["d_model"], 2 * d["n_kv_heads"] * d["head_dim"]),
            "wo": (hd, d["d_model"]),
            "w_in": (d["d_model"], 2 * d["d_ff"]),
            "w_out": (d["d_ff"], d["d_model"])}


def make_base(d: dict, seed: int):
    """The frozen float32 base, made on the device in one jitted call.
    Projections are N(0, 1/fan_in); the two residual outputs are scaled
    by 1/sqrt(2 * layers); embeddings are N(0, 0.02**2)."""
    G, V, dm = d["n_layers"], d["vocab_size"], d["d_model"]
    sh = shapes(d)

    def build(key):
        ks = iter(jax.random.split(key, 8))

        def normal(shape, std):
            return std * jax.random.normal(next(ks), shape, jnp.float32)

        res = 1.0 / math.sqrt(2 * G)
        layer = {"ln": jnp.ones((G, dm)), "ln2": jnp.ones((G, dm))}
        for name, (i, o) in sh.items():
            scale = res if name in ("wo", "w_out") else 1.0
            layer[name] = normal((G, i, o), scale / math.sqrt(i))
        p = {"embed": normal((V, dm), 0.02), "final_norm": jnp.ones((dm,)),
             "groups": (layer,)}
        if not d["tie_embeddings"]:
            p["lm_head"] = normal((dm, V), 1.0 / math.sqrt(dm))
        return p

    return jax.jit(build)(jax.random.PRNGKey(seed))


def make_adapters(d: dict, n_clients: int, seed: int):
    """Stacked initial adapters (C, layers, ...): A ~ N(0, 1/d_in),
    B = 0, as in Hu et al. (2021)."""
    G, r = d["n_layers"], d["rank"]
    sh = shapes(d)

    def build(key):
        ks = jax.random.split(key, len(sh))
        layer = {}
        for k, (name, (i, o)) in zip(ks, sorted(sh.items())):
            layer[f"{name}_lora_a"] = jax.random.normal(
                k, (n_clients, G, i, r), jnp.float32) / math.sqrt(i)
            layer[f"{name}_lora_b"] = jnp.zeros((n_clients, G, r, o))
        return {"groups": (layer,)}

    return jax.jit(build)(jax.random.PRNGKey(seed))
