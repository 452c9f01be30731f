"""Arcee's afmoe (Trinity-Mini): leading dense layers, routed experts with a
shared one, window and full attention in one layer pattern. README.md beside
this file says what the family reads and where its limits come from."""
