"""repro: factorized zero-copy all-to-all for multidimensional tori
(Träff, CS.DC 2026) — JAX/TPU training & serving framework."""

__version__ = "1.0.0"
