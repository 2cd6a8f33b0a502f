"""Plain float32 references the checks compare against; they import nothing of the program."""
