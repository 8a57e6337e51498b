"""Pallas TPU kernels (validated in interpret mode on CPU).

gf256_encode.py     bit-sliced GF(2^8) matmul (RS encode / decode) and
                    stream scaling
xor_reduce.py       parity-accumulator XOR fold
ops.py              the coding layer's one dispatcher into them; ref.py:
                    pure-jnp oracles
flash_attention.py  flash-attention forward (VMEM online softmax) of the
                    model-training scaffold; no storage path calls it
"""
