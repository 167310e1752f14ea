"""Found by name from the data files; see ../harness.py."""
