"""Suite-wide setup: JAX runs on the CPU backend unless told otherwise."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
