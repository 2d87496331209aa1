"""The benchmark of volsync-tpu: see README.md beside this file."""
