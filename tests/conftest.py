import os

# Tests run against a single CPU device, with Pallas kernels in interpret
# mode (the dry-run asks for its 512 host devices in its own process).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
