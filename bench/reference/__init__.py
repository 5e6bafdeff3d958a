"""Plain float32 reference of one EF21-SGDM training step at the cells'
sizes. It imports nothing from the program under test: weights and data
come from the seed by the same recipes, computed here."""
