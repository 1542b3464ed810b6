"""Open-loop serving traffic for a cell of the Olmo-Hybrid family: the
schedule is `open_loop_poisson`'s (independent users, Poisson arrivals at a
fixed ``rate``, one fixed replayed trace of lengths and due times drawn from
the cell's own ``schedule_seed``; ``--seed`` draws weights and what the
prompts say only). What differs is the system that serves it: bf16 weights
and a cache that holds recurrent states beside KV rows
(`systems/engine_olmo_hybrid.py`)."""

from __future__ import annotations

from .open_loop_poisson import schedule  # noqa: F401

SYSTEM = "engine_olmo_hybrid"
