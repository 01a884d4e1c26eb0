"""ps_pytorch_tpu — a TPU-native synchronous parameter-server training framework.

A brand-new JAX/XLA/Pallas re-design (not a port) with the capabilities of the
reference mpi4py/PyTorch parameter-server implementation (see SURVEY.md):

- Models: LeNet, ResNet-18/34/50/101/152, VGG-11/13/16/19 (+/- BN)
  (reference: src/model_ops/*, src/util.py:8-19)
- Optimizers: SGD (momentum/nesterov/dampening/weight-decay) and Adam (AMSGrad)
  with PyTorch update semantics (reference: src/optim/sgd.py, src/optim/adam.py)
- Datasets: MNIST, CIFAR-10/100, SVHN with the reference's normalization and
  augmentation (reference: src/util.py:21-106) — augmentation runs on-device.
- Parameter-server data parallelism over a `jax.sharding.Mesh`: replicated
  params, per-worker gradients, `lax.psum` aggregation with partial
  ("backup-worker") num-aggregate masking, optional int8-quantized collectives
  (Pallas kernel) replacing Blosc compression, and a ZeRO-1 style sharded
  optimizer-state mode (the "PS chip" generalized to a sharded PS).
  (reference: src/sync_replicas_master_nn.py, src/distributed_worker.py,
   src/compression.py)
- Checkpointing with step-tagged single-writer checkpoints + actual resume,
  and an out-of-band polling evaluator (reference: src/distributed_evaluator.py).
"""

__version__ = "0.1.0"
