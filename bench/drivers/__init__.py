"""One driver per traffic kind, found by the mix's ``driver`` name."""
