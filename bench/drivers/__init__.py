"""One driver per traffic kind: set-up, window and check of a cell."""
