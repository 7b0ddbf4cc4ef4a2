"""Plain float32 reference of the dense GQA decoder, independent of the program."""
