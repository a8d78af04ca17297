"""repro: DeFT (flexible communication scheduling) reproduction on JAX."""
