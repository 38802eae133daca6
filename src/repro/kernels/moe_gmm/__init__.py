"""Grouped matmul over tokens sorted by expert (drop-free routed MoE)."""
