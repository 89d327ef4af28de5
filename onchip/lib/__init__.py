"""The yardstick of the on-chip benchmark: everything a number is made
from that a later PR must not be able to move.  Nothing here imports the
program (`stark_tpu`)."""
