"""The benchmark's yardstick; imports nothing of the program under test."""
