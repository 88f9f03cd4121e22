"""Cell drivers, one per traffic kind, found by name."""
