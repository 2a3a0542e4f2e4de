"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel subpackage follows the contract:
  kernel.py — ``pl.pallas_call`` + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper (``interpret=True`` on CPU hosts;
              ``cluster_batch`` takes its jnp oracle off the TPU instead)
  ref.py    — pure-jnp oracle the tests sweep against

Kernels:
  edge_score      — 2PS-L two-candidate scoring (the paper's O(|E|) hot loop)
  hdrf_score      — HDRF k-way scoring (the O(|E|*k) baseline hot loop)
  cluster_batch   — 2PS-L clustering micro-batch, written in place (Phase 1)
  spmm            — CSR row-blocked A @ X message passing (GNN)
  flash_attention — blockwise online-softmax GQA attention (LM)
  embedding_bag   — ragged gather + segment-sum pooling (recsys)
  augru           — attention-gated GRU scan (DIEN)
"""
