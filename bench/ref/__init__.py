"""The benchmark's plain reference, written from the deployment's stated
rules and importing nothing of the program under test."""
