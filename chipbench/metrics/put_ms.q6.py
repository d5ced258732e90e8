"""Mean host-to-HBM put of one Q6 offload through NvmCsd, in milliseconds:
``tier.put`` spans, one an offload, from the start of the put until the
extent's device buffer is ready (``put_ms.csd``'s reading)."""
import named

read = named.load("metrics", "put_ms.csd").read
