"""Plain float32 references of the benchmark's configurations; they
import nothing of the program."""
