"""MPIgnite-JAX: MPI-style peer/collective communication as a first-class
layer of a multi-pod JAX training & serving framework.

See README.md / DESIGN.md. Public surface:

- ``repro.core``      -- the paper's contribution (communicators, closures)
- ``repro.models``    -- the 10 assigned architectures behind one Model
- ``repro.parallel``  -- ShardOps/GlobalOps distribution paths
- ``repro.train``     -- optimizers, steps, checkpointing, fault tolerance
- ``repro.serve``     -- continuous-batching engine
- ``repro.kernels``   -- Pallas TPU kernels (+ jnp oracles)
- ``repro.launch``    -- meshes, compile cache, serve and train drivers
"""

__version__ = "1.0.0"
