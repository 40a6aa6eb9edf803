"""The benchmark's harness: the yardstick that later changes to the
program are measured with.  Nothing here is imported by the program."""
