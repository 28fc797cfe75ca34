"""Pallas TPU kernels for the paper's compute hot-spots (DESIGN.md §8).

dispatch.py    — THE backend seam: ref|pallas|auto registry behind one typed,
                 batch-first kernel contract (KernelBackend)
clause_eval.py — clause evaluation as an int8 MXU matmul (the paper's
                 2-cycle inference datapath, recast for the systolic array);
                 batched form evaluates all B datapoints per include-bank
                 read; packed form evaluates uint32 literal words as
                 AND + popcount (the FPGA's bit-level datapath, §13)
packing.py     — the bit-packed literal layout: uint32 words, two-half
                 [pack(x), pack(~x)] literal split, tail-bit contract
feedback.py    — fused Type I/II TA-bank update (one VPU pass per datapoint)
ops.py         — public wrappers; dispatch binds interpret mode from the
                 platform, and on a mesh each kernel runs under shard_map
ref.py         — pure-jnp oracles; kernels are asserted bit-exact vs these
"""
