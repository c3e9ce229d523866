"""How the Pallas kernels are named in a TPU profiler trace (regular
expressions over the ``XLA Ops`` event names)."""

OTA_ROUND = r"ota_round"
OTA_SHARD_TX = r"shard_tx"
