"""Plain float32 reference of each model family (one file each, named as
the family), independent of the program."""
