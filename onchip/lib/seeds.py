"""Seeds: any whole number (the driver's are over 2**31) to 31-bit words."""

import numpy as np


def seed_words(seed):
    """Two 31-bit words from any whole-number seed."""
    w = np.random.SeedSequence(int(seed)).generate_state(2)
    return int(w[0] >> 1), int(w[1] >> 1)
