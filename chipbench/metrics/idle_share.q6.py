"""Share of the traced window in which no operation ran on the device, in
percent (one minus busy over window): ``idle_share.csd``'s reading, in the
Q6 cell."""
import named

read = named.load("metrics", "idle_share.csd").read
