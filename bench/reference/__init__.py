"""Plain float32 references: written from the published model descriptions,
importing nothing of the system under test."""
