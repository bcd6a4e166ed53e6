"""One module per job kind, found by a configuration's ``kind``."""
