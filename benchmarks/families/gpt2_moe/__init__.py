"""The GPT-2 trunk with routed experts in place of each block's MLP
(`models/moe.py`, registry family `gpt2_moe`): the rehearsal family that
went through the seam as new files only (PR 29). CPU rehearsals only; no
configuration of `BENCHMARK.json` names it."""
