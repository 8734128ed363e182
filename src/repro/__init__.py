"""ZeRO-Infinity reproduction: three-tier (HBM / host / NVMe) ZeRO training
in JAX, with a GSPMD-native engine and a paper-faithful explicit-collective
engine behind one executor interface (see ``repro.core.executor``).
"""
