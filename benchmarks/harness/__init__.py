"""The benchmark's own code: everything that decides a number lives here,
where a PR that changes the program cannot change it."""
