# Pallas kernels.  partition_reduce holds the engine's fused partition
# kernels; flash_attention and ssd_scan serve the LM stack, which the engine
# does not reach; ref.py holds the plain oracles the tests check them with.
