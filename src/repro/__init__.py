"""repro: Col-Bandit late-interaction retrieval framework (JAX/Pallas)."""

__version__ = "0.1.0"
