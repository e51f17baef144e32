"""One runner per configuration ``kind``."""
