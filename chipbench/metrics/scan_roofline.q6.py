"""Share of the HBM roofline the Q6 page scans through NvmCsd reached: the
least time the chip could take to read every record of every scanned extent
once (bytes over peak HBM bandwidth) over the kernel time in the device
trace, in percent (``scan_roofline.csd``'s reading)."""
import named

read = named.load("metrics", "scan_roofline.csd").read
