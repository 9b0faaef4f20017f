"""repro: MATCHA decentralized-SGD reproduction on jax."""
