"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel ships three pieces: the pallas_call + BlockSpec implementation
(<name>.py), a jit'd public wrapper (ops.py), and a pure-jnp oracle
(ref.py) that the test suite sweeps shapes/dtypes against.

  mover.py            fused PIC particle push (the paper's hot spot)
  deposit.py          one-hot CIC charge deposition
  flash_attention.py  grouped-GQA flash attention (LM substrate hot spot)
  interleave.py       the engine's queue merge back to slot order (its
                      oracles are the index forms in
                      tests/test_queue_layout.py)

On this CPU container kernels run in interpret mode (correctness); on TPU
they compile through Mosaic with the documented VMEM tilings.
"""
