"""Plain float32 references the check compares the program with; they
import nothing of the program."""
