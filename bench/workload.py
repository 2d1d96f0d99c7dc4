"""The one generator of traffic: reads a mix from ``traffic/<name>.json``.

A mix states the workflow DAG (stages, their models, parents and planner
hints), the queries per workflow, the prompt and generation lengths, and
the arrival process:

- ``"loop": "open"``: Poisson arrivals at ``rate_per_s``.  The arrival
  times come from the mix's own ``arrival_seed``, so every run offers the
  same schedule; the run's seed changes the prompts and the weights.
- ``"loop": "closed"``: one client that sends the next workflow when the
  last one has finished.

The DAG of ``traffic/agentic.*.json`` is a copy of
``repro.workflowbench.suites.agentic_workflow``, kept here as data so that
the yardstick does not move with the program.
"""
from __future__ import annotations

import numpy as np


class Traffic:
    def __init__(self, mix: dict):
        self.mix = mix
        self.loop = mix["loop"]
        if self.loop not in ("open", "closed"):
            raise ValueError(f"unknown loop {self.loop!r}")
        self.queries = int(mix["queries"])
        self.prompt_len = int(mix["prompt_len"])
        self.gen_len = int(mix["gen_len"])
        self.stages = mix["dag"]

    def arrivals(self, seconds: float) -> list[float]:
        """Offsets in seconds from the window's start of the workflows due
        in it (open loop)."""
        rng = np.random.default_rng(self.mix["arrival_seed"])
        rate = float(self.mix["rate_per_s"])
        out, t = [], 0.0
        while True:
            t += rng.exponential(1.0 / rate)
            if t >= seconds:
                return out
            out.append(t)

    def prompts(self, seed: int, n: int, vocab: int) -> np.ndarray:
        """Prompt token ids of ``n`` workflows: ``[n, queries,
        prompt_len]`` int32, a function of the seed alone."""
        rng = np.random.default_rng([seed, 0x70726F6D])
        return rng.integers(0, vocab, (n, self.queries, self.prompt_len),
                            dtype=np.int32)

    def workflow(self, wid: str):
        """The mix's DAG as the program's ``Workflow``."""
        from repro.core.workflow import Stage, Workflow
        stages = {
            sid: Stage(sid, s["model"], base_cost={-1: s["base_cost"]},
                       prefix_group=s.get("prefix_group"),
                       max_shards=s.get("max_shards", 1),
                       output_tokens=s.get("output_tokens", 256.0),
                       parents=tuple(s.get("parents", ())))
            for sid, s in self.stages.items()}
        return Workflow(wid=wid, stages=stages, num_queries=self.queries)

    def tokens_per_stage(self) -> int:
        """Tokens a stage serves: every query's prompt and its output."""
        return self.queries * (self.prompt_len + self.gen_len)
