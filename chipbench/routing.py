"""Traffic generation: routing draws, payloads and seeds.

The generators are copies of the program's own
(``benchmarks.common.moe_load_fractions`` and ``chip_smoke.dispatch_matrix``),
kept here so that no change to the program can change the traffic.
"""
from __future__ import annotations

import numpy as np


def load_fractions(n_experts: int, zipf_s: float, seed: int) -> np.ndarray:
    """Expert loads ~ 1 / rank**zipf_s (uniform at 0), shuffled by
    ``seed``."""
    rng = np.random.default_rng(seed)
    w = 1.0 / np.arange(1, n_experts + 1) ** zipf_s
    return rng.permutation(w / w.sum())


def dispatch_matrix(n_experts: int, top_k: int, chips: int, tokens: int,
                    zipf_s: float, seed: int) -> np.ndarray:
    """S[i][j]: rows chip ``i`` sends to chip ``j`` when every chip holds
    ``tokens`` tokens, each routed to ``top_k`` experts under the loads of
    :func:`load_fractions`, and chip ``j`` holds experts
    ``j*E/chips .. (j+1)*E/chips - 1``."""
    chip_load = load_fractions(n_experts, zipf_s, seed).reshape(
        chips, -1).sum(axis=1)
    rng = np.random.default_rng(seed)
    return np.stack([rng.multinomial(tokens * top_k, chip_load)
                     for _ in range(chips)]).astype(np.int64)


def routing_pool(config: dict, traffic: dict) -> list[np.ndarray]:
    """The traffic file's fixed pool of dispatch matrices."""
    r = traffic["routing"]
    n_experts = (config["n_routed_experts"] if "n_routed_experts" in config
                 else config["num_local_experts"])
    return [dispatch_matrix(n_experts,
                            config["num_experts_per_tok"],
                            config["expert_parallel_chips"],
                            traffic["tokens_per_chip"], r["zipf_s"], s)
            for s in r["seeds"]]


def cycle_order(n: int, seed: int) -> np.ndarray:
    """The order in which ``--seed`` cycles a pool of ``n`` draws."""
    return np.random.default_rng(seed_words(seed)).permutation(n)


def seed_words(seed: int) -> list[int]:
    """``seed`` (any integer) as two 32-bit words."""
    seed %= 2 ** 64
    return [seed % 2 ** 32, seed >> 32]


def payload_key(seed: int):
    """The JAX key every payload of a run is drawn from."""
    import jax

    lo, hi = seed_words(seed)
    return jax.random.fold_in(jax.random.PRNGKey(lo), np.uint32(hi))


def payload(key, shape, dtype):
    """Random rows of ``dtype`` with no NaN or infinity: random bits with
    the top exponent bit cleared, so a bitwise comparison is exact."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype)
    uint = {2: jnp.uint16, 4: jnp.uint32}[dtype.itemsize]
    bits = jax.random.bits(key, shape, uint)
    mask = ~(1 << (8 * dtype.itemsize - 2)) & ((1 << 8 * dtype.itemsize) - 1)
    return jax.lax.bitcast_convert_type(bits & uint(mask), dtype)


# exponent and mantissa bits of the types a control computes through
LOWER = {"float8_e4m3fn": (4, 3)}


def lowered(x, precision: str):
    """``x`` rounded to ``precision`` and held in its own type.  An
    explicit ``reduce_precision``: a round trip through ``astype`` is
    folded away by XLA on the TPU, which may keep the excess precision."""
    import jax

    exponent, mantissa = LOWER[precision]
    return jax.lax.reduce_precision(x, exponent_bits=exponent,
                                    mantissa_bits=mantissa)


def as_bits(x):
    """``x`` bit for bit as unsigned integers."""
    import jax
    import jax.numpy as jnp

    uint = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
    return jax.lax.bitcast_convert_type(x, uint)
