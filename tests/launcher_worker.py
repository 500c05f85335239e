"""Worker for the launcher test: relies entirely on the env wiring that
``python -m horovod_tpu.run`` provides (HVD_COORDINATOR_ADDRESS /
HVD_NUM_PROCESSES / HVD_PROCESS_ID, and JAX_PLATFORMS under --cpu)."""

import numpy as np
import jax.numpy as jnp

import horovod_tpu as hvd

hvd.init()
assert hvd.num_processes() == 2, hvd.num_processes()
assert hvd.size() == 8, hvd.size()

out = np.asarray(hvd.allreduce(jnp.ones((2,)), average=False))
np.testing.assert_allclose(out, np.full((2,), 8.0))
print(f"rank {hvd.rank()} (proc {hvd.process_index()}): LAUNCHER TEST PASSED",
      flush=True)
