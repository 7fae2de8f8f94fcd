"""Model zoo: flax models designed mesh-first.

Every model ships with a `param_specs` giving the PartitionSpec tree for its
parameters (dp/fsdp/tp/sp axes), so trainers shard by annotation and XLA
inserts the collectives — the GSPMD replacement for the reference's
DDP/FSDP/vLLM-TP delegation (train/torch/config.py:36, vllm_models.py:123).

Importing the package imports no model: `published.py` needs no JAX.
"""
