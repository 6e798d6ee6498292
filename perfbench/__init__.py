"""Chip benchmark of the serving stack: one cell (configuration x traffic
mix) per run, driven through ``LLMService`` on a TPU. ``run.py`` is the
entry point; ``BENCHMARK.json`` at the root of the checkout names the cells.
"""
