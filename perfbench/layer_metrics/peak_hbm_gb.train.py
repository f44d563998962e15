"""Peak device memory of a training cell."""
from perfbench.harness.readers import peak_hbm_gb as read  # noqa: F401
