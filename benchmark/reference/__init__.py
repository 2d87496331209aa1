"""The plain reference: hashlib and byte counts, nothing of the program."""
