"""orion-tpu: a TPU-native LLM training and inference framework.

Brand-new implementation with the capabilities of the reference CUDA/NCCL
stack ``DatCorno/orion`` (see SURVEY.md), re-designed for TPU: XLA collectives
over ICI/DCN on a named ``jax.sharding.Mesh`` instead of NCCL process groups;
DP/FSDP/TP/PP/SP/EP as mesh axes and sharding rules instead of wrapper
modules; Pallas kernels instead of CUDA; a single jit-compiled train step with
optax + Orbax instead of an eager step loop; and a paged-KV continuous
batching engine for inference.
"""

__version__ = "0.1.0"
